"""Controls: runs of a cell with one guarantee broken on purpose.

    python3 benchmark/control.py --workload <name> --seed <n> --seconds <s> --control <kind>

Each control has to come out ``"correct": false``; its readings of the
compared numbers are the upper readings their limits are set below
(PERF.md). The benchmark's own runs never run a control.

``--control host`` is a run of its own, and its result line is the
control's. The other controls alter a copy of what the window produced
and print each verdict on an earlier line, ``{"control": ...}``; the
run's own result line stays that of the unbroken output.

* ``host``: the program's host engine (``--strategy bfs``) in place of
  the device path, the step a later change could take to look faster
  on a cell that requires the device: breaks device participation.
* ``pc``: every reported issue names the next byte of the code as its
  location: an answer altered where it is produced.
* ``witness``: the first calldata byte (the function selector) of every
  witness's last transaction is altered: a wrong witness.
* ``drop-half``: every other analysis reports no issue: half of the
  answers left out.
"""

import copy
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench  # noqa: E402
from benchmark.lib.check import judge, passed  # noqa: E402


def _shift_pc(result):
    for a in result.analyses:
        for issue in a.issues:
            issue["address"] = issue["address"] + 1


def _alter_selector(result):
    for a in result.analyses:
        for issue in a.issues:
            step = issue["tx_sequence"]["steps"][-1]
            data = step["input"][2:]
            flipped = "%02x" % (int(data[:2] or "00", 16) ^ 0xFF)
            step["input"] = "0x" + flipped + data[2:]


def _drop_half(result):
    for a in result.analyses[::2]:
        a.issues = []


TAMPERS = {"pc": _shift_pc, "witness": _alter_selector, "drop-half": _drop_half}
CONTROLS = ("host",) + tuple(TAMPERS)


def _judge_each(kinds, device_required):
    """Judge a copy of the window's output under each tamper; the run's
    own line stays the unbroken one."""

    def tamper(result):
        for kind in kinds:
            broken = types.SimpleNamespace(analyses=copy.deepcopy(result.analyses))
            TAMPERS[kind](broken)
            checks = judge(broken.analyses, device_required)
            bench.note(control=kind, correct=passed(checks), checks=checks)

    return tamper


def prepare(run, args):
    kinds = args.control.split(",")
    bad = [k for k in kinds if k not in CONTROLS]
    if bad or ("host" in kinds and len(kinds) > 1):
        raise SystemExit("control: give host alone, or any of %s" % ", ".join(TAMPERS))
    bench.note(control=kinds)
    if kinds == ["host"]:
        run.config["settings"]["strategy"] = "bfs"
        return None
    return _judge_each(kinds, bool(run.traffic.get("device_required")))


def add_control(parser):
    parser.add_argument("--control", required=True,
                        help="host, or a comma-separated list of %s" % ", ".join(TAMPERS))


if __name__ == "__main__":
    code = bench.main(extra=add_control, prepare=prepare)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
