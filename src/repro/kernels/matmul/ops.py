"""Public entry point for MULTIPLY: the compiled Pallas kernel on TPU, the
jnp oracle elsewhere (``repro.kernels.backend.resolve``). ``use_kernel=False``
forces the oracle (used by the benchmarks to isolate kernel effects)."""
from __future__ import annotations

from ..backend import resolve
from .matmul import matmul as _matmul_kernel_call
from .ref import matmul_ref


def matmul(x, y, *, bm: int = 128, bn: int = 128, bk: int = 128,
           use_kernel: bool | None = None):
    use, interpret = resolve(use_kernel)
    if not use:
        return matmul_ref(x, y)
    return _matmul_kernel_call(x, y, bm=bm, bn=bn, bk=bk, interpret=interpret)
