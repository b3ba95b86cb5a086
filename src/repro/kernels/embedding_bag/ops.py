from __future__ import annotations

from ..backend import resolve
from .embedding_bag import embedding_bag as _kernel
from .ref import embedding_bag_ref


def embedding_bag(table, indices, weights=None, *, use_kernel: bool | None = None):
    use, interpret = resolve(use_kernel)
    if not use:
        return embedding_bag_ref(table, indices, weights)
    return _kernel(table, indices, weights, interpret=interpret)
