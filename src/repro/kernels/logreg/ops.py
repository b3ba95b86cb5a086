from __future__ import annotations

from ..backend import resolve
from .logreg import logreg_grad as _kernel
from .ref import logreg_grad_ref


def logreg_grad(x, y, w, *, bn: int = 512, use_kernel: bool | None = None):
    use, interpret = resolve(use_kernel)
    if not use:
        return logreg_grad_ref(x, y, w)
    return _kernel(x, y, w, bn=bn, interpret=interpret)
