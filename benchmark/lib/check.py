"""The comparison that decides ``correct``.

Every analysis that the window drove is judged once the window has
closed, against the plain reference:

* ``missing_hazards``: hazards that the contract's source documents
  (``benchmark/contracts/<name>.json``), reachable within the analysis's
  transaction count, that the report does not name by SWC id and pc;
* ``unconfirmed_witnesses``: reported issues whose witness transaction
  sequence, replayed on the reference EVM (benchmark/lib/evm.py), does
  not show the hazard at the reported pc;
* ``failed_analyses``: analyses that raised;
* ``host_only_analyses``: where the traffic asks the device to take part,
  analyses whose window retired no instruction on the device.

Each is an exact count, so each limit is 0.
"""

from typing import Dict, List, Optional

from benchmark.lib import evm
from benchmark.lib.redeploy import load_contract


class Analysis:
    """What one analysis in the window produced."""

    def __init__(self, deployment, tx_count: int, issues: Optional[List[dict]],
                 error: Optional[str] = None, device_steps: Optional[int] = None):
        self.deployment = deployment
        self.tx_count = tx_count
        self.issues = issues or []
        self.error = error
        self.device_steps = device_steps


def judge(analyses: List[Analysis], device_required: bool) -> Dict[str, Dict[str, int]]:
    missing = unconfirmed = failed = host_only = 0
    hazards_of: Dict[str, list] = {}
    for a in analyses:
        if a.error is not None:
            failed += 1
            continue
        name = a.deployment.contract
        if name not in hazards_of:
            hazards_of[name] = load_contract(name)["hazards"]
        reported = {(str(i.get("swc-id")), i.get("address")) for i in a.issues}
        for hazard in hazards_of[name]:
            if hazard["min_tx"] <= a.tx_count and (hazard["swc"], hazard["pc"]) not in reported:
                missing += 1
        for issue in a.issues:
            try:
                rep = evm.replay(issue.get("tx_sequence"), a.deployment.creation)
                why = evm.confirms(issue, a.deployment.runtime, rep)
            except evm.ReplayError as e:
                why = str(e)
            if why is not None:
                unconfirmed += 1
        if device_required and not a.device_steps:
            host_only += 1
    checks = {
        "missing_hazards": missing,
        "unconfirmed_witnesses": unconfirmed,
        "failed_analyses": failed,
    }
    if device_required:
        checks["host_only_analyses"] = host_only
    return {name: {"value": value, "limit": 0} for name, value in checks.items()}


def passed(checks: Dict[str, Dict[str, int]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
