"""Parallel GCDA operators (paper §5.4, Table 3) + matrix generation.

* Matrix generation: ``rel2matrix_host`` (local access — columnar reads, no
  tuple-at-a-time scan) and ``random_access_matrix`` (aggregate multi-valued
  attributes from qualifying records into multi-hot / count features).
* Analytical operators: MULTIPLY / SIMILARITY / REGRESSION, block-tiled
  Pallas kernels; optionally distributed with ``shard_map`` over a device
  mesh (the paper's worker threads -> mesh shards).
* ``volcano`` submodule: a literal tuple-at-a-time volcano implementation of
  the same operators — the ablation baseline (GredoDB-S / GredoDB-D rely on
  volcano-model execution for GCDA in §7.2).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.cosine_sim.ops import cosine_sim as _cosine_op
from ..kernels.logreg.ops import logreg_grad as _logreg_op
from ..kernels.matmul.ops import matmul as _matmul_op
from .storage import DictColumn, RaggedColumn, Table


# ---------------------------------------------------------------------------
# Matrix generation (G in Eq. 5)
# ---------------------------------------------------------------------------


def rel2matrix_host(table: Table, columns: Sequence[str]) -> np.ndarray:
    """REL2MATRIX's host assembly: numeric columns into an (n, k) float32
    matrix straight from columnar storage (bypasses row iteration)."""
    cols = []
    for c in columns:
        col = table.col(c)
        if isinstance(col, DictColumn):
            cols.append(col.codes.astype(np.float32))
        else:
            cols.append(np.asarray(col, dtype=np.float32))
    return np.stack(cols, axis=1)


def rel2matrix_sharded(table: Table, columns: Sequence[str], k: int
                       ) -> tuple[jax.Array, dict]:
    """Born-sharded REL2MATRIX: each contiguous row block is cast to float32
    and staged to the device independently, then the blocks are concatenated
    *device-side* — the downstream GCDA kernels (MatMul / Similarity /
    Regression) consume the result without a host gather. Values are
    bit-identical to :func:`rel2matrix_host` (same per-element float32
    cast, same row order). With more than one device the blocks land on a 1-D ``data``
    mesh via :class:`NamedSharding`; on a single device the block layout
    still avoids materializing the full host-side matrix at once.

    Returns ``(matrix, spec)`` where ``spec`` is the sharding provenance the
    executor attaches to the operator's trace span (``born_sharded``,
    ``host_gather``, ``shards``, ``sharding``)."""
    from .storage import shard_bounds
    cols = [table.col(c) for c in columns]
    blocks = []
    rows_per_block = []
    for lo, hi in shard_bounds(table.nrows, k):
        if lo >= hi:
            continue
        parts = []
        for col in cols:
            if isinstance(col, DictColumn):
                parts.append(col.codes[lo:hi].astype(np.float32))
            else:
                parts.append(np.asarray(col)[lo:hi].astype(np.float32))
        blocks.append(jnp.asarray(np.stack(parts, axis=1)))
        rows_per_block.append(hi - lo)
    if not blocks:
        mat = jnp.zeros((0, len(columns)), dtype=jnp.float32)
    elif len(blocks) == 1:
        mat = blocks[0]
    else:
        mat = jnp.concatenate(blocks, axis=0)
    devices = jax.devices()
    if len(devices) > 1 and len(blocks) > 1:
        ndev = min(len(devices), len(blocks))
        mesh = Mesh(np.array(devices[:ndev]), ("data",))
        mat = jax.device_put(mat, NamedSharding(mesh, P("data", None)))
        sharding = f"NamedSharding(mesh=data:{ndev}, spec=P('data', None))"
    else:
        plat = devices[0].platform if devices else "cpu"
        sharding = f"blocks={len(blocks)} device={plat}"
    spec = {"born_sharded": True, "host_gather": False,
            "shards": int(k), "sharding": sharding,
            "rows_per_block": rows_per_block}
    return mat, spec


def random_access_matrix_host(table: Table, group_col: str, value_col: str,
                              n_features: int, mode: str = "multi_hot"
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Random access's host assembly — aggregate (multi-valued) attributes
    of qualifying records into per-group feature rows. Returns (matrix,
    group_ids): row i holds the multi-hot / count vector of ``value_col``
    over group i."""
    groups = np.asarray(table.col(group_col))
    vcol = table.col(value_col)
    if isinstance(vcol, RaggedColumn):
        rows = np.repeat(groups, vcol.lengths())
        vals = np.asarray(vcol.values)
    else:
        rows = groups
        vals = np.asarray(vcol)
    uniq, row_idx = np.unique(rows, return_inverse=True)
    mat = np.zeros((len(uniq), n_features), dtype=np.float32)
    ok = (vals >= 0) & (vals < n_features)
    np.add.at(mat, (row_idx[ok], vals[ok].astype(np.int64)), 1.0)
    if mode == "multi_hot":
        mat = np.minimum(mat, 1.0)
    return mat, uniq


def random_access_matrix(table: Table, group_col: str, value_col: str,
                         n_features: int, mode: str = "multi_hot"
                         ) -> tuple[jax.Array, np.ndarray]:
    """Random access: :func:`random_access_matrix_host` with the matrix on
    the device."""
    mat, uniq = random_access_matrix_host(table, group_col, value_col,
                                          n_features, mode)
    return jnp.asarray(mat), uniq


# ---------------------------------------------------------------------------
# Analytical operators (A in Eq. 5): block-parallel Pallas execution
# ---------------------------------------------------------------------------


def multiply(x: jax.Array, y: jax.Array, *, mesh: Optional[Mesh] = None,
             use_kernel: bool | None = None) -> jax.Array:
    """MULTIPLY: Z = X·Y via the tiled MXU kernel; with a mesh, Z tiles are
    sharded (i over 'data', j over 'model') and each shard runs the local
    kernel — the distributed form of the paper's block scheduler."""
    if mesh is None:
        return _matmul_op(x, y, use_kernel=use_kernel)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
    return jax.jit(jnp.dot, out_shardings=NamedSharding(mesh, P("data", "model")))(xs, ys)


def similarity(x: jax.Array, y: jax.Array, *, mesh: Optional[Mesh] = None,
               use_kernel: bool | None = None) -> jax.Array:
    """SIMILARITY: pairwise cosine scores via the fused kernel."""
    if mesh is None:
        return _cosine_op(x, y, use_kernel=use_kernel)
    from ..kernels.cosine_sim import cosine_sim_ref
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("model", None)))
    return jax.jit(cosine_sim_ref,
                   out_shardings=NamedSharding(mesh, P("data", "model")))(xs, ys)


@functools.partial(jax.jit,
                   static_argnames=("iters", "lr", "l2", "use_kernel"))
def _regression_loop(x, y, *, iters: int, lr: float, l2: float,
                     use_kernel: bool | None):
    def step(_, carry):
        w, _ = carry
        g, loss = _logreg_op(x, y, w, use_kernel=use_kernel)
        return w - lr * (g + l2 * w), loss

    w0 = jnp.zeros((x.shape[1],), jnp.float32)
    return jax.lax.fori_loop(0, iters, step, (w0, jnp.float32(0)))


def regression(x: jax.Array, y: jax.Array, *, iters: int = 100,
               lr: float = 0.5, l2: float = 1e-4,
               use_kernel: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """REGRESSION: train a logistic-regression model with the fused
    gradient kernel inside a lax loop. Returns (weights, final loss). One
    jitted program per (shape, iters, lr, l2, use_kernel), shared by every
    call."""
    return _regression_loop(x, y, iters=int(iters), lr=float(lr),
                            l2=float(l2), use_kernel=use_kernel)


def regression_distributed(x: jax.Array, y: jax.Array, mesh: Mesh, *,
                           iters: int = 50, lr: float = 0.5, l2: float = 1e-4
                           ) -> tuple[jax.Array, jax.Array]:
    """Data-parallel REGRESSION: rows sharded over 'data'; each shard
    computes its partial gradient, one psum per iteration (the paper's
    "aggregating contributions from each partition in parallel")."""
    n, d = x.shape
    ndev = mesh.shape["data"]
    pad = (-n) % ndev
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    yp = jnp.pad(y, (0, pad))

    @jax.jit
    def run(xs, ys):
        def local_grad(xs_, ys_, w):
            z = xs_ @ w
            p = jax.nn.sigmoid(z)
            gpart = xs_.T @ (p - ys_)
            lpart = jnp.sum(jax.nn.softplus(z) - ys_ * z)
            g = jax.lax.psum(gpart, "data") / n
            loss = jax.lax.psum(lpart, "data") / n
            return g, loss

        sharded = jax.shard_map(local_grad, mesh=mesh,
                                in_specs=(P("data", None), P("data"), P()),
                                out_specs=(P(), P()))

        def step(carry, _):
            w, _ = carry
            g, loss = sharded(xs, ys, w)
            return (w - lr * (g + l2 * w), loss), None

        (w, loss), _ = jax.lax.scan(step, (jnp.zeros((d,), jnp.float32),
                                           jnp.float32(0)), None, length=iters)
        return w, loss

    return run(xp, yp)


# ---------------------------------------------------------------------------
# Volcano baseline: tuple-at-a-time GCDA (ablation §7.2)
# ---------------------------------------------------------------------------


class volcano:
    """Literal tuple-at-a-time execution of the same analytics — each value
    flows through a Python-level iterator chain (the paper's criticism:
    excessive iterator invocations, function-call overhead, no batching)."""

    @staticmethod
    def rel2matrix(table: Table, columns: Sequence[str]) -> np.ndarray:
        out = []
        for i in range(table.nrows):          # tuple at a time
            row = []
            for c in columns:
                col = table.col(c)
                v = col.codes[i] if isinstance(col, DictColumn) else np.asarray(col)[i]
                row.append(float(v))
            out.append(row)
        return np.asarray(out, dtype=np.float32)

    @staticmethod
    def multiply(x: np.ndarray, y: np.ndarray) -> np.ndarray:
        m, k = x.shape
        k2, n = y.shape
        z = np.zeros((m, n), dtype=np.float32)
        for i in range(m):
            for j in range(n):
                acc = 0.0
                for l in range(k):
                    acc += float(x[i, l]) * float(y[l, j])
                z[i, j] = acc
        return z

    @staticmethod
    def similarity(x: np.ndarray, y: np.ndarray) -> np.ndarray:
        m, n = x.shape[0], y.shape[0]
        out = np.zeros((m, n), dtype=np.float32)
        for i in range(m):
            for j in range(n):
                dot = nx = ny = 0.0
                for l in range(x.shape[1]):
                    dot += float(x[i, l]) * float(y[j, l])
                    nx += float(x[i, l]) ** 2
                    ny += float(y[j, l]) ** 2
                out[i, j] = dot / max((nx ** 0.5) * (ny ** 0.5), 1e-12)
        return out

    @staticmethod
    def regression(x: np.ndarray, y: np.ndarray, iters: int = 100,
                   lr: float = 0.5, l2: float = 1e-4) -> tuple[np.ndarray, float]:
        n, d = x.shape
        w = np.zeros(d, dtype=np.float64)
        loss = 0.0
        for _ in range(iters):
            g = np.zeros(d, dtype=np.float64)
            loss = 0.0
            for i in range(n):                 # tuple at a time
                z = 0.0
                for l in range(d):
                    z += float(x[i, l]) * w[l]
                p = 1.0 / (1.0 + np.exp(-z))
                err = p - float(y[i])
                for l in range(d):
                    g[l] += err * float(x[i, l])
                loss += np.logaddexp(0.0, z) - float(y[i]) * z
            w -= lr * (g / n + l2 * w)
        return w.astype(np.float32), float(loss / n)
