"""What accelerator this host has, found without initializing jax.

A process that initializes jax on a TPU host takes every chip it can
see until it exits, so code that only needs to know what is there (the
compile-cache location, how many fleet workers may hold a chip) must
not ask jax.
"""

import glob
import os


def host_chip_count() -> int:
    """Accelerator chips this host exposes, counted from their device
    nodes (TPU VMs expose /dev/accel<N> or /dev/vfio/<N>) without
    initializing jax, which would take the chips."""
    return len(glob.glob("/dev/accel[0-9]*")) + len(glob.glob("/dev/vfio/[0-9]*"))


def cpu_requested() -> bool:
    """True when JAX_PLATFORMS asks for the CPU backend first. The one
    place that reads the variable for that question."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def runs_on_cpu() -> bool:
    """True when jax will run on its CPU backend: JAX_PLATFORMS selects
    cpu, or it is unset on a host with no accelerator chip."""
    if os.environ.get("JAX_PLATFORMS", "").strip():
        return cpu_requested()
    return host_chip_count() == 0
