"""Virtual CPU devices for the multi-device paths' rehearsals.

`jax_num_cpu_devices` is the one way to get them on the installed jax
(0.9.0 ignores the old XLA_FLAGS form), and it can only be set before the
first backend starts — afterwards the update raises. Callers that may run
inside an already-started process (the test suite's conftest has asked for
eight) go through `request()`.
"""
from __future__ import annotations


def request(n: int) -> bool:
    """Ask the CPU backend for `n` virtual devices. A no-op (False) once
    any backend exists: whoever started it decided the device count."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        return False
    jax.config.update("jax_num_cpu_devices", n)
    return True
