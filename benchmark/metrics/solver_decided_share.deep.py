"""Solver: the share of the window's ``decide_batch`` queries (frontier
and triage) that came back decided, by memo, rewrite, static seed,
witness reuse, device or host, and not unknown."""

from benchmark.lib.program_counts import counter_total, window_events


def read(result):
    events = window_events()
    if events is None:
        return None
    queries = counter_total(events, "myth_solver_batch_queries_total")
    if queries <= 0:
        return None
    unknown = counter_total(events, "myth_solver_outcomes_total", outcome="unknown")
    return (queries - unknown) / queries
