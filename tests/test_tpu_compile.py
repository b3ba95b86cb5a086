"""Compile the engine's device programs for a described TPU v5e chip, at the
shapes the M2Bench deployment puts on the chip at sf=16. Nothing runs: the
TPU compiler is installed without a chip, so this catches tiling, memory
and lowering refusals before any chip time is spent.

The topology is described inside a module-scoped fixture and never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cosine_sim.cosine_sim import cosine_sim
from repro.kernels.logreg.logreg import logreg_grad
from repro.kernels.matmul.matmul import matmul
from repro.kernels.traversal import ops as traversal_ops

# sf=16 shapes (m2bench.generate(sf=16, seed=0)): the A2/A3 multi-hot
# matrix (customers x tags) and the a_shard_reg feature matrix
# (q_shard_join rows x 4 columns)
MULTI_HOT = (25_524, 200)
SHARD_REG = (93_254, 4)
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("op", ["matmul", "cosine_sim", "logreg_grad"])
def test_gcda_kernel_compiles_for_v5e(op, one_chip):
    n, d = MULTI_HOT
    if op == "matmul":          # A3: the Gram product x @ x.T
        fn = lambda x, y: matmul(x, y, interpret=False)
        args = (_spec((n, d), one_chip), _spec((d, n), one_chip))
    elif op == "cosine_sim":    # A2: self-similarity
        fn = lambda x, y: cosine_sim(x, y, interpret=False)
        args = (_spec((n, d), one_chip), _spec((n, d), one_chip))
    else:                       # a_shard_reg: one gradient step
        rows, cols = SHARD_REG
        fn = lambda x, y, w: logreg_grad(x, y, w, interpret=False)
        args = (_spec((rows, cols), one_chip), _spec((rows,), one_chip),
                _spec((cols,), one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


# the device-chain launches the optimizer picks at sf=16: G3's two hops over
# the Follows CSR, and q_range_narrow's one hop over the Interested_in CSR
# (vertices, edges, hops, capacity)
CHAINS = {"q_g3_follows": (40_000, 199_665, 2, 1 << 19),
          "q_range_narrow_interested_in": (40_200, 320_355, 1, 1 << 14)}
# a hop's cost on the chip is its count of capacity-wide gathers: seven a hop
# and four for the path re-join at two hops; a binary-search expansion would
# bring back a loop of gathers per hop
MAX_GATHERS = {"q_g3_follows": 18}


@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_device_chain_program_compiles_for_v5e(chain, one_chip):
    """The ``device-chain`` access path: the whole chain as one XLA program
    (no Pallas kernel in it) at the capacity the optimizer chose, with no
    loop in it and no more gathers than the hop design needs."""
    n_vertices, n_edges, hops, cap = CHAINS[chain]
    chunk = 2048
    n_chunks = -(-n_edges // chunk)
    i32 = lambda shape: _spec(shape, one_chip, jnp.int32)
    b8 = lambda shape: _spec(shape, one_chip, jnp.bool_)
    compiled = traversal_ops._chain_device.lower(
        i32((n_vertices + 1,)), i32((n_edges,)), i32((n_edges,)),
        i32((cap,)), b8((cap,)),
        (b8((n_vertices,)),) * hops, (b8((n_edges,)),) * hops,
        (b8((n_chunks,)),) * hops,
        capacity=cap, chunk=chunk, use_kernel=False, interpret=False,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not re.search(r"\swhile\(", text)
    if chain in MAX_GATHERS:
        assert len(re.findall(r"\sgather\(", text)) <= MAX_GATHERS[chain]
    assert _fits(compiled)


# LDBC SNB SF10's knows graph: 65,645 persons (65,664 padded to 128 lanes),
# 1,938,516 undirected edges stored in both directions
SNB_VERTICES, SNB_PADDED, SNB_EDGES = 65_645, 65_664, 3_877_032


@pytest.mark.parametrize("program", ["forward", "backward", "pair"])
def test_reach_program_compiles_for_v5e(program, one_chip):
    """The device reach: a ``while`` over BFS levels, each an int8 product
    of the frontier (or, two-sided, both frontiers) with the dense
    adjacency (int32 accumulator), and the one scatter that builds the
    adjacency from the CSR; both fit one chip."""
    i32 = lambda shape: _spec(shape, one_chip, jnp.int32)
    adj = _spec((SNB_PADDED, SNB_PADDED), one_chip, jnp.int8)
    member = _spec((SNB_PADDED,), one_chip, jnp.bool_)
    compiled = traversal_ops.reach_program.lower(
        adj, i32((2 if program == "pair" else 1,)), member, i32(()), i32(()),
        transpose=program == "backward").compile()
    text = compiled.as_text()
    assert re.search(r"\swhile\(", text)
    assert "tpu_custom_call" not in text
    assert _fits(compiled)
    build = traversal_ops.dense_adjacency.lower(
        i32((SNB_VERTICES + 1,)), i32((SNB_EDGES,)),
        n_pad=SNB_PADDED).compile()
    m = build.memory_analysis()
    assert m.output_size_in_bytes == SNB_PADDED * SNB_PADDED
    assert _fits(build)
