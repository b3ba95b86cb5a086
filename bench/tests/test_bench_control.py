"""The check that decides ``correct``, at sf=1 on the CPU.

The control (for GCDI the reference's answer with repeated rows dropped,
for GCDIA the reference in bfloat16, in the program's place) has to fail a
limit; a run with the timed path's answers altered
where they are produced has to come out not correct; a sound run has to
come out correct. The same control runs on the chip at the cells' size
through ``bench/control.py``.
"""
import numpy as np
import pytest

from bench import check, reference, run, workload
from bench.gen import m2b_ecom

SEED = 2**31 + 999


def small(name):
    _, w, cfg, mix = run.cell(name)
    return dict(cfg, sf=1), mix


@pytest.fixture(scope="module")
def answers():
    """One program answer per pool entry template of each mix, at sf=1; the
    session's in its order from a cleared inter-buffer, so that A2 and A1
    take A3's matrix from it."""
    import jax.numpy as jnp
    from repro.core import GredoEngine
    db, raw = m2b_ecom.generate(1, SEED)
    m2b_ecom.build_indexes(db)
    eng = GredoEngine(db, mode="gredo")
    out = {}
    for mix_name in ("gcdi_mix", "gcdia_cold", "gcdia_session"):
        mix = workload.load_mix(mix_name)
        pool = workload.build_pool(mix, SEED, raw, m2b_ecom)
        eng.interbuffer.clear()
        kept = []
        for name in mix.get("session", pool):
            e = pool[name][0]
            if mix["kind"] == "query":
                t = eng.query(e.request)
                kept.append((e, [np.asarray(t.col(a)) for a in e.spec["select"]],
                             None))
            elif e.spec["analytics"]["op"] == "REGRESSION":
                kept.append((e, np.asarray(eng.analyze(e.request,
                                                       iters=mix["iters"])),
                             None))
            else:
                y = eng.analyze(e.request, iters=mix["iters"])
                rows = np.arange(0, y.shape[0], 53)
                kept.append((e, np.asarray(y[jnp.asarray(rows)]), rows))
        out["session" if "session" in mix else mix["kind"]] = (kept, raw, mix)
    return out


@pytest.mark.parametrize("kind", ["query", "analyze", "session"])
def test_program_passes_and_control_fails(answers, kind):
    kept, raw, mix = answers[kind]
    kind = mix["kind"]
    ok, table = check.verdict(kind, check.numbers(kind, kept, raw, mix, 0,
                                                  ref=reference))
    assert ok, table
    ok, table = check.verdict(kind, check.numbers(kind, kept, raw, mix, 0,
                                                  ref=reference,
                                                  control=True))
    assert not ok, table
    if kind == "analyze":
        # bfloat16 breaks SIMILARITY and REGRESSION; 0/1 products stay exact
        assert table["sim_err"]["value"] > 3 * table["sim_err"]["limit"]
        assert table["reg_err"]["value"] > 3 * table["reg_err"]["limit"]
    else:
        assert table["wrong_rows"]["value"] > 0


def _drop_last_row(t):
    return t.take(np.arange(max(t.nrows - 1, 0)))


@pytest.mark.parametrize("name", ["ecom_gcdi_mix", "ecom_gcdia_cold",
                                  "ecom_gcdia_session"])
def test_run_is_correct_and_an_altered_answer_is_not(monkeypatch, name):
    from repro.core import GredoEngine
    cfg, mix = small(name)
    line = run.run_cell(name, SEED, 1.0, False, config=cfg)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    if mix["kind"] == "query":
        real = GredoEngine.query
        monkeypatch.setattr(GredoEngine, "query",
                            lambda self, q: _drop_last_row(real(self, q)))
    else:
        real = GredoEngine.analyze
        monkeypatch.setattr(GredoEngine, "analyze",
                            lambda self, t, **kw: real(self, t, **kw) + 1e-3)
    line = run.run_cell(name, SEED, 1.0, False, config=cfg)
    assert not line["correct"], line["checks"]


def test_a_wrong_interbuffer_hit_is_not_correct(monkeypatch):
    """At sf=1 a session's A2 and A1 take A3's matrix from the inter-buffer
    (n x n = 1,617^2 floats fit in it); a hit that hands back an altered
    matrix has to come out not correct."""
    from repro.core.interbuffer import InterBuffer
    name = "ecom_gcdia_session"
    cfg, _ = small(name)
    hits = []
    real = InterBuffer.get

    def wrong(self, key):
        got = real(self, key)
        if got is None or hasattr(got, "columns"):
            return got
        hits.append(key)
        return got.at[0].set(1.0 - got[0])
    monkeypatch.setattr(InterBuffer, "get", wrong)
    line = run.run_cell(name, SEED, 1.0, False, config=cfg)
    assert hits and not line["correct"], line["checks"]
