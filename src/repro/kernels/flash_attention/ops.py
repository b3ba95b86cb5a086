from __future__ import annotations

from ..backend import resolve
from .flash_attention import flash_attention as _kernel
from .ref import flash_attention_ref


def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    bq: int = 128, bk: int = 128, use_kernel: bool | None = None):
    use, interpret = resolve(use_kernel)
    if not use:
        return flash_attention_ref(q, k, v, lengths, causal=causal)
    return _kernel(q, k, v, lengths, causal=causal, bq=bq, bk=bk,
                   interpret=interpret)
