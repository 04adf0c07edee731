"""When the window opens and what it counts: protocol v1 opening at
transaction k."""

import pytest

from benchmark.lib.meter import SteadyStateMeter


class _Strategy:
    def __init__(self):
        self.device_steps_retired = 0


class _Laser:
    def __init__(self):
        self.total_states = 0
        self.strategy = _Strategy()
        self.hooks = []

    def register_laser_hooks(self, kind, hook):
        assert kind == "start_sym_trans"
        self.hooks.append(hook)

    def transaction(self, host_states, device_steps):
        for hook in self.hooks:
            hook()
        self.total_states += host_states
        self.strategy.device_steps_retired += device_steps


@pytest.mark.parametrize("k", [1, 2, 3])
def test_protocol_v1_window_opens_at_transaction_k(k):
    laser, opened = _Laser(), []
    meter = SteadyStateMeter(open_at_tx=k, on_open=opened.append)
    meter.install(laser)
    work = [(700, 18), (240, 84924), (3, 0)]
    for host, device in work:
        laser.transaction(host, device)
    meter.close()
    assert opened == [laser]
    assert len(meter.tx_starts) == 3
    assert meter.states == sum(h + d for h, d in work[k - 1:])
    assert meter.device_steps == sum(d for _, d in work[k - 1:])
    assert meter.wall > 0


def test_a_window_that_never_opens_measures_nothing():
    laser = _Laser()
    meter = SteadyStateMeter(open_at_tx=2)
    meter.install(laser)
    laser.transaction(10, 0)
    meter.close()
    assert meter.t_open is None and meter.t_close is None

