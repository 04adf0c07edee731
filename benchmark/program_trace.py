"""A traced run of a cell that also reports what the program's own
span tracer holds, on an earlier line of standard output:

    python3 benchmark/program_trace.py --workload <name> --seed <n> --seconds <s> --trace 1

``{"program_trace": ...}`` gives the tracer's event count and drops;
the offset between the profiler's copies of the program's spans (each
span's ``TraceAnnotation``) and the same spans put on the trace's clock
through the harness's one anchor; ``host_sat`` seconds by the phase
around each solve; ``decide_batch`` queries and outcomes by use; the
lane-steps and lane-step slots of the fused loop; and the warmup's
seconds per stage. The run's own result line follows as usual. The
benchmark's own runs never run this.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench  # noqa: E402
from benchmark.lib import program_counts as pc  # noqa: E402


def prepare(run, args):
    def report(result):
        from mythril_tpu import obs

        events = obs.TRACER.raw_events()
        fields = {"events": len(events), "dropped": obs.TRACER.dropped}
        capture = run.capture
        if capture is not None and capture.xspace and capture.t_traced is not None:
            fields["annotation_offset"] = pc.annotation_offsets(
                capture.xspace, events, capture.t_traced)
        fields["host_sat_by_phase_s"] = pc.enclosing_split(events, "host_sat")
        fields["host_sat_solves"] = pc.counter_by_label(
            events, "myth_host_sat_solves_total", "result")
        fields["settle_s"] = pc.span_seconds(events, "settle")
        fields["queries_by_use"] = pc.counter_by_label(
            events, "myth_solver_batch_queries_total", "use")
        fields["outcomes"] = {
            use: pc.counter_by_label(
                [e for e in events if e[0] == "C" and e[6]["labels"].get("use") == use],
                "myth_solver_outcomes_total", "outcome")
            for use in fields["queries_by_use"]
        }
        fields["lane_steps"] = pc.counter_total(events, "myth_device_steps_total")
        fields["lane_slots"] = pc.counter_total(events, "myth_device_lane_slots_total")
        fields["fused_super_rounds"] = [
            dict(e[6], wall_s=e[5]) for e in events
            if e[0] == "X" and e[1] == "fused_super_round"]
        fields["warmup_s"] = pc.registry_series(
            obs.REGISTRY.snapshot(), "myth_warmup_s", "stage")
        bench.note(program_trace=fields)

    return report


if __name__ == "__main__":
    code = bench.main(prepare=prepare)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
