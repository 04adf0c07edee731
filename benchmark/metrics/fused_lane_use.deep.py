"""Kernels: lane-steps the device retired over the lane-step slots the
fused loop issued (rounds x steps per round x lanes), window counts."""

from benchmark.lib.program_counts import counter_total, window_events


def read(result):
    events = window_events()
    if events is None:
        return None
    slots = counter_total(events, "myth_device_lane_slots_total")
    if slots <= 0:
        return None
    return counter_total(events, "myth_device_steps_total") / slots
