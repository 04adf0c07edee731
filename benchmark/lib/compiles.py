"""Count XLA compilations with a ``jax.monitoring`` listener.

JAX reports every compile request, whether XLA compiles the program or
loads it from the persistent compilation cache, as one
``backend_compile_duration`` event naming the jitted function. Either
means that a program was not ready in memory: inside a measured window
there should be none, because set-up warms every shape up.
"""

import threading

_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self._lock = threading.Lock()
        self.names = []  # jitted function of each compile request

    @property
    def count(self) -> int:
        return len(self.names)

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        with self._lock:
            if event == _COMPILE:
                self.names.append(kwargs.get("fun_name"))
