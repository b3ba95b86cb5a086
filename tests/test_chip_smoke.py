"""chip_smoke.py off the chip: it refuses to run without a TPU, and its GCDI
and device-traversal phases hold on the CPU backend at sf=1 (the GCDA phase
needs the compiled Pallas kernels and runs only on the chip)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--sf", "1"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke
    from repro.data import m2bench
    db = m2bench.generate(sf=1, seed=0)
    m2bench.build_indexes(db)
    eng = chip_smoke.GredoEngine(db, mode="gredo")
    ref = chip_smoke.GredoEngine(db, mode="single")
    return chip_smoke, eng, ref, chip_smoke.gcdi_queries(db)


def test_smoke_gcdi_phase_matches_single_engine(smoke):
    cs, eng, ref, queries = smoke
    device, detail = cs.phase_gcdi(eng, ref, queries)
    assert "q_g3" in device
    assert "gredo==single" in detail


def test_smoke_traversal_phase_survives_write_burst(smoke):
    cs, eng, ref, queries = smoke
    g = eng.db.graphs["Follows"]
    epoch0 = g.epoch
    _, detail = cs.phase_traversal(eng, ref, queries, ["q_g3"])
    assert "compacted->device" in detail
    assert g.epoch > epoch0 and not g.delta.has_pending()

