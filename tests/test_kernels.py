"""Per-kernel correctness: shape/dtype sweeps, interpret-mode pallas_call vs
the pure-jnp ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cosine_sim.cosine_sim import cosine_sim
from repro.kernels.cosine_sim.ref import cosine_sim_ref
from repro.kernels.embedding_bag.embedding_bag import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.logreg.logreg import logreg_grad
from repro.kernels.logreg.ref import logreg_grad_ref
from repro.kernels.matmul.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n", [(32, 32, 32), (128, 128, 128),
                                   (100, 60, 130), (257, 129, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    x = jnp.asarray(RNG.standard_normal((m, k)), dtype)
    y = jnp.asarray(RNG.standard_normal((k, n)), dtype)
    out = matmul(x, y, bm=32, bn=32, bk=32, interpret=True)
    ref = matmul_ref(x, y)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,d", [(64, 64, 32), (100, 50, 96), (33, 65, 17)])
def test_cosine_sweep(m, n, d):
    x = jnp.asarray(RNG.standard_normal((m, d)), jnp.float32)
    y = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    out = cosine_sim(x, y, bm=32, bn=32, bk=32, interpret=True)
    np.testing.assert_allclose(out, cosine_sim_ref(x, y), rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("n,d,bn", [(100, 16, 32), (512, 64, 128), (65, 7, 16)])
def test_logreg_sweep(n, d, bn):
    x = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    y = jnp.asarray(RNG.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(d) * 0.3, jnp.float32)
    g1, l1 = logreg_grad(x, y, w, bn=bn, interpret=True)
    g2, l2 = logreg_grad_ref(x, y, w)
    np.testing.assert_allclose(g1, g2, rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(l1, l2, rtol=3e-4)


@pytest.mark.parametrize("b,h,hk,sq,skv,causal", [
    (2, 4, 4, 64, 64, True),      # MHA train
    (2, 8, 2, 100, 100, True),    # GQA, ragged seq
    (3, 8, 2, 1, 256, True),      # decode
    (2, 4, 2, 48, 96, False),     # bidirectional, q != kv
])
def test_flash_attention_sweep(b, h, hk, sq, skv, causal):
    q = jnp.asarray(RNG.standard_normal((b, h, sq, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hk, skv, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hk, skv, 64)), jnp.float32)
    lens = jnp.asarray(RNG.integers(max(sq, 1), skv + 1, b), jnp.int32)
    out = flash_attention(q, k, v, lens, causal=causal, bq=32, bk=32,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, lens, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("nbags,bag,V,D", [(8, 4, 64, 16), (16, 8, 500, 32)])
def test_embedding_bag_sweep(nbags, bag, V, D):
    table = jnp.asarray(RNG.standard_normal((V, D)), jnp.float32)
    idx = RNG.integers(0, V, (nbags, bag)).astype(np.int32)
    idx[0, 1:] = -1
    w = jnp.asarray(RNG.random((nbags, bag)), jnp.float32)
    out = embedding_bag(table, jnp.asarray(idx), w, interpret=True)
    ref = embedding_bag_ref(table, jnp.asarray(idx), w)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-5)


def test_flash_matches_model_dense_attention():
    """Kernel agrees with the model's dense attention oracle path."""
    from repro.models.transformer import _dense_attention
    q = jnp.asarray(RNG.standard_normal((2, 4, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 2, 32, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 2, 32, 16)), jnp.float32)
    lens = jnp.full((2,), 32, jnp.int32)
    out = flash_attention(q, k, v, lens, causal=True, bq=16, bk=16,
                          interpret=True)
    ref = _dense_attention(q, k, v, lens, True)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-5)


def test_engine_import_initialises_no_backend():
    """Kernel dispatch picks its backend per call: importing the engine
    must not initialise JAX (a process that only imports it must not take
    the chip)."""
    import os
    import subprocess
    import sys
    code = ("import repro.core, repro.data.m2bench, repro.kernels\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_resolve_picks_oracle_off_tpu_and_interpreter_on_request():
    from repro.kernels.backend import resolve
    assert jax.default_backend() != "tpu"
    assert resolve(None) == (False, False)
    assert resolve(True) == (True, True)
    assert resolve(False) == (False, False)
