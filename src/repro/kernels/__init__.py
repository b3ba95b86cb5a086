"""Pallas TPU kernels. Each subpackage ships <name>.py (pl.pallas_call +
BlockSpec), ops.py (dispatch, decided per call by ``backend.resolve``: the
compiled kernel on TPU, the oracle elsewhere), ref.py (pure-jnp oracle)."""
from .cosine_sim import cosine_sim, cosine_sim_ref
from .embedding_bag import embedding_bag, embedding_bag_ref
from .flash_attention import flash_attention, flash_attention_ref
from .logreg import logreg_grad, logreg_grad_ref
from .matmul import matmul, matmul_ref

__all__ = [
    "matmul", "matmul_ref", "cosine_sim", "cosine_sim_ref",
    "logreg_grad", "logreg_grad_ref", "flash_attention",
    "flash_attention_ref", "embedding_bag", "embedding_bag_ref",
]
