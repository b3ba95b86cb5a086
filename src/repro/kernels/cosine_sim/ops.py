from __future__ import annotations

from ..backend import resolve
from .cosine_sim import cosine_sim as _kernel
from .ref import cosine_sim_ref


def cosine_sim(x, y, *, bm: int = 128, bn: int = 128, bk: int = 128,
               use_kernel: bool | None = None):
    use, interpret = resolve(use_kernel)
    if not use:
        return cosine_sim_ref(x, y)
    return _kernel(x, y, bm=bm, bn=bn, bk=bk, interpret=interpret)
