"""Which program a kernel call runs, decided when the call is made.

The choice is never made at import: asking JAX for its backend initialises
it, and a process that merely imports the engine must not take the chip.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(use_kernel: bool | None) -> tuple[bool, bool]:
    """``(run the Pallas kernel, run it under the Pallas interpreter)`` for
    a call made now. ``None`` picks the compiled kernel on the TPU and the
    jnp oracle on any other backend; ``True`` off the TPU runs the kernel in
    interpret mode (how the tests check it on CPU); ``False`` always runs
    the oracle."""
    tpu = on_tpu()
    use = tpu if use_kernel is None else bool(use_kernel)
    return use, use and not tpu
