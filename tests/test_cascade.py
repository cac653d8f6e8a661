"""The multi-fidelity evaluation cascade: fidelity keys and cache
non-aliasing (including under concurrent submit/map across the thread,
process, and service backends), the per-rung scorer paths (rung 1 agreeing
with HloAnalysis.summary totals, rung 2's deterministic modelled timer),
successive-halving promotion counts, the residual-driven calibration EMA and
its persistence, engine bit-identity with promotion disabled, and kill/
resume replay of promotion + correction decisions."""
import concurrent.futures as cf
import functools
import threading

import pytest

from repro.core import (Archipelago, InlineBackend, ProcessBackend,
                        ScoreCache, Scorer, make_backend, seed_genome)
from repro.core.evals import (FIDELITIES, HLO, MEASURED, PERFMODEL,
                              CascadeBackend, EvalSpec, fidelity_key,
                              intern_spec, key_fidelity)
from repro.core.evals.scorer import PROXY_SEQ, _correctness_proxy_shapes
from repro.core.perfmodel import (BenchConfig, PerfModelCalibration, estimate,
                                  measured_estimate)

FAST_SUITE = [BenchConfig("c4k", 8, 16, 16, 4096, causal=True),
              BenchConfig("n4k", 8, 16, 16, 4096, causal=False)]


# -- fidelity keys -------------------------------------------------------------


def test_fidelity_key_roundtrip():
    gk = seed_genome().key()
    assert fidelity_key(gk) == gk                      # rung 0 = bare key
    assert fidelity_key(gk, PERFMODEL) == gk
    for fid in (HLO, MEASURED):
        k = fidelity_key(gk, fid)
        assert k != gk and k.startswith(fid + "::")
        assert key_fidelity(k) == fid
    assert key_fidelity(gk) == PERFMODEL
    with pytest.raises(ValueError, match="unknown fidelity"):
        fidelity_key(gk, "oracle")


def test_eval_spec_carries_fidelity_with_distinct_wire_ids():
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    assert spec.fidelity == PERFMODEL
    rung1 = spec.with_fidelity(HLO)
    assert rung1.suite == spec.suite and rung1.fidelity == HLO
    # value-based interning: each rung is its own spec on the wire
    ids = {intern_spec(spec.with_fidelity(f)) for f in FIDELITIES}
    assert len(ids) == len(FIDELITIES)
    with pytest.raises(ValueError, match="unknown fidelity"):
        EvalSpec.resolve(FAST_SUITE, fidelity="oracle")


def test_scorer_rejects_unknown_fidelity():
    with pytest.raises(ValueError, match="unknown fidelity"):
        Scorer(suite=FAST_SUITE, fidelity="oracle")


def test_score_cache_stats_counts_per_fidelity():
    cache = ScoreCache()
    g = seed_genome()
    for fid in FIDELITIES:
        Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache,
               fidelity=fid)(g)
    stats = cache.stats()
    assert stats["entries"] == 3
    assert stats["per_fidelity"] == {PERFMODEL: 1, HLO: 1, MEASURED: 1}
    assert stats["misses"] == 3 and stats["hits"] == 0
    Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache,
           fidelity=HLO)(g)                            # cache hit, no re-trace
    assert cache.stats()["hits"] == 1


# -- per-rung scoring ----------------------------------------------------------


def test_rungs_score_one_genome_differently_without_aliasing():
    cache = ScoreCache()
    g = seed_genome()
    svs = {fid: Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache,
                       fidelity=fid)(g) for fid in FIDELITIES}
    assert all(sv.correct for sv in svs.values())
    vals = {fid: sv.values for fid, sv in svs.items()}
    assert vals[PERFMODEL] != vals[HLO] != vals[MEASURED]
    assert vals[PERFMODEL] != vals[MEASURED]
    assert len(cache) == 3                             # no rung aliased another


def test_rung1_agrees_with_hlo_summary_totals():
    """The hlo rung's value must be exactly the roofline formula applied to
    an independently produced HloAnalysis.summary of the same proxy trace."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention
    from repro.launch.hlo_analysis import HloAnalysis, roofline_terms
    suite = [FAST_SUITE[0]]                            # one causal config
    g = seed_genome()
    sv = Scorer(suite=suite, check_correctness=False, fidelity=HLO)(g)

    kw = g.kernel_kwargs()
    kw["block_q"] = max(16, min(kw["block_q"], 2048) // 16)
    kw["block_k"] = max(16, min(kw["block_k"], 2048) // 16)
    shape = jax.ShapeDtypeStruct((1, 4, PROXY_SEQ, 64), jnp.float32)
    fn = functools.partial(flash_attention, causal=True, window=None,
                           interpret=True, **kw)
    summary = HloAnalysis(
        jax.jit(fn).lower(shape, shape, shape).compile().as_text()).summary()
    assert summary["flops"] > 0 and summary["bytes_accessed"] > 0
    expected = Scorer.roofline_tflops(summary)
    assert sv.values[0] == pytest.approx(expected, rel=0, abs=0)
    # and the formula is the max of the shared three-term model
    assert max(roofline_terms(summary).values()) > 0


def test_measured_rung_is_deterministic_and_term_scaled():
    g = seed_genome()
    cfg = FAST_SUITE[0]
    p0, pm = estimate(g, cfg), measured_estimate(g, cfg)
    assert measured_estimate(g, cfg).tflops == pm.tflops     # deterministic
    assert pm.total_s > p0.total_s and pm.tflops < p0.tflops
    assert pm.t_mxu == p0.t_mxu                              # mxu factor 1.0
    assert pm.t_bubble > p0.t_bubble


def test_measured_rung_names_its_timer():
    """On a CPU-only process rung 2 is the modelled timer and says so; the
    lower rungs run no timer."""
    g = seed_genome()
    sv = Scorer(suite=FAST_SUITE, check_correctness=False,
                fidelity=MEASURED)(g)
    assert sv.timer == "modelled"
    assert Scorer(suite=FAST_SUITE, check_correctness=False)(g).timer == ""


def test_proxy_window_derives_from_suite_not_constant():
    """Satellite fix: two suites with distinct window sets must stop
    collapsing onto one w=48 proxy shape."""
    narrow = [BenchConfig("w", 8, 16, 16, 4096, causal=True, window=256)]
    wide = [BenchConfig("w", 8, 16, 16, 4096, causal=True, window=2048)]
    w_narrow = _correctness_proxy_shapes(narrow)[0]["window"]
    w_wide = _correctness_proxy_shapes(wide)[0]["window"]
    assert w_narrow != w_wide
    assert 16 <= w_narrow < w_wide <= PROXY_SEQ - 32
    # window-free configs keep a full-attention proxy
    assert _correctness_proxy_shapes(FAST_SUITE)[0]["window"] is None


# -- calibration ---------------------------------------------------------------


def test_calibration_ema_and_state_roundtrip():
    cal = PerfModelCalibration(alpha=0.5)
    cal.observe("dma", predicted=10.0, measured=8.0)
    assert cal.correction("dma") == pytest.approx(0.8)
    assert cal.correction("mxu") == 1.0                # unseen class: identity
    cal.observe("dma", predicted=10.0, measured=4.0)   # EMA, not replacement
    assert cal.correction("dma") == pytest.approx(0.5 * 0.8 + 0.5 * 0.4)
    assert cal.corrected("dma", 100.0) == pytest.approx(100.0 * cal.correction("dma"))
    cal.observe("vpu", predicted=0.0, measured=5.0)    # failed eval: no signal
    assert "vpu" not in cal.factors
    clone = PerfModelCalibration()
    clone.load_state(cal.state())
    assert clone.state() == cal.state()
    with pytest.raises(ValueError):
        PerfModelCalibration(alpha=0.0)


# -- cascade promotion ---------------------------------------------------------


def _rung_backends(cache):
    mk = lambda fid: make_backend(  # noqa: E731
        "inline", suite=FAST_SUITE, check_correctness=False, cache=cache,
        fidelity=fid)
    return [mk(PERFMODEL), mk(HLO), mk(MEASURED)]


def _slate(n):
    g = seed_genome()
    edits = [dict(block_q=256), dict(block_k=256), dict(kv_in_grid=True),
             dict(mask_mode="block_skip"), dict(rescale_mode="branchless"),
             dict(div_mode="deferred"), dict(block_q=64)]
    return [g] + [g.with_(**e) for e in edits[:n - 1]]


def test_cascade_promotes_at_most_one_over_eta_per_rung():
    cache = ScoreCache()
    casc = CascadeBackend(_rung_backends(cache), eta=3)
    log = casc.run_cascade(_slate(7))
    assert log["evals"][PERFMODEL] == 7
    assert log["evals"][HLO] == 7 // 3 == 2
    assert log["evals"][MEASURED] == 1                 # max(1, 2 // 3)
    assert log["promoted"][MEASURED][0] in log["promoted"][HLO]
    assert casc.calibration.observations == 1
    stats = cache.stats()
    assert stats["per_fidelity"][HLO] == 2
    assert stats["per_fidelity"][MEASURED] == 1


def test_cascade_promotion_disabled_is_rung0_only():
    cache = ScoreCache()
    casc = CascadeBackend(_rung_backends(cache), eta=2)
    log = casc.run_cascade(_slate(6), promote=False)
    assert log["evals"] == {PERFMODEL: 6, HLO: 0, MEASURED: 0}
    assert cache.stats()["per_fidelity"] == {PERFMODEL: 6}
    assert casc.calibration.observations == 0


def test_cascade_dedups_slate_and_handles_empty():
    casc = CascadeBackend(_rung_backends(ScoreCache()), eta=2)
    g = seed_genome()
    assert casc.run_cascade([g, g, g])["slate"] == 1
    assert casc.run_cascade([])["slate"] == 0


def test_cascade_rejects_bad_shape():
    with pytest.raises(ValueError, match="at least"):
        CascadeBackend([], eta=2)
    with pytest.raises(ValueError, match="eta"):
        CascadeBackend(_rung_backends(ScoreCache()), eta=1)
    with pytest.raises(ValueError, match="at most"):
        CascadeBackend(_rung_backends(ScoreCache()) * 2, eta=2)


def test_cascade_delegates_backend_surface_to_rung0():
    cache = ScoreCache()
    rungs = _rung_backends(cache)
    casc = CascadeBackend(rungs, eta=2)
    g = seed_genome()
    assert casc.suite == rungs[0].suite
    assert casc(g).values == rungs[0](g).values
    assert casc.score_key(g) == g.key()                # rung-0 key, bare
    assert [sv.values for sv in casc.map([g])] == [casc(g).values]
    assert casc.submit(g).result().values == casc(g).values
    assert casc.baselines() == rungs[0].baselines()


# -- concurrent non-aliasing across backends -----------------------------------


def _fidelity_pair(name):
    """(rung0, rung2, finalizers) sharing ONE cache on backend ``name``."""
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    cache = ScoreCache()
    if name == "thread":
        mk = lambda s: make_backend("thread", suite=s, cache=cache,  # noqa: E731
                                    max_workers=2)
        return mk(spec), mk(spec.with_fidelity(MEASURED)), []
    if name == "process":
        # one injected executor for both rungs, like the engine does
        pool = cf.ThreadPoolExecutor(max_workers=2)
        b0 = ProcessBackend(spec=spec, executor=pool, cache=cache)
        b2 = ProcessBackend(spec=spec.with_fidelity(MEASURED), executor=pool,
                            cache=cache)
        return b0, b2, [lambda: pool.shutdown(wait=True)]
    if name == "service":
        from repro.core.evals import ServiceBackend
        from repro.core.evals.service_worker import EvalServiceWorker
        b0 = ServiceBackend(spec=spec, workers=0, cache=cache)
        b2 = ServiceBackend(spec=spec.with_fidelity(MEASURED),
                            coordinator=b0.coordinator, cache=cache)
        w = EvalServiceWorker(*b0.address, slots=2, name="cascade-test")
        t = threading.Thread(target=w.run, daemon=True)
        t.start()
        assert b0.coordinator.wait_for_workers(1, timeout=10)
        return b0, b2, [w.stop, lambda: t.join(5)]
    raise AssertionError(name)


@pytest.mark.parametrize("name", ("thread", "process", "service"))
def test_fidelity_rungs_never_alias_under_concurrent_submit_map(name):
    """A genome scored at rung 0 re-scores at rung 2 — never a cache hit on
    the cheap result — even when both rungs hammer one shared cache
    concurrently through submit AND map."""
    b0, b2, finalizers = _fidelity_pair(name)
    try:
        genomes = _slate(4)
        with cf.ThreadPoolExecutor(max_workers=4) as racers:
            f0 = racers.submit(b0.map, genomes)
            f2 = racers.submit(b2.map, genomes)
            extra = [racers.submit(b.submit, g).result()
                     for b in (b0, b2) for g in genomes]
            svs0, svs2 = f0.result(timeout=120), f2.result(timeout=120)
            for f in extra:
                f.result(timeout=120)
        assert [sv.values for sv in svs0] != [sv.values for sv in svs2]
        cache = b0.cache
        assert cache is b2.cache
        stats = cache.stats()
        assert stats["per_fidelity"] == {PERFMODEL: len(genomes),
                                         MEASURED: len(genomes)}
        for g in genomes:                    # both rungs cached, independently
            assert cache.peek(g.key()) is not None
            assert cache.peek(fidelity_key(g.key(), MEASURED)) is not None
    finally:
        b2.close()
        b0.close()
        for fin in finalizers:
            fin()


# -- engine integration --------------------------------------------------------


def _fingerprints(tmp_path=None, tag="", steps=4, **kw):
    eng = Archipelago(n_islands=2, suite=FAST_SUITE, migration_interval=2,
                      seed=11, backend="thread", check_correctness=False,
                      persist_path=str(tmp_path / f"arch{tag}.json")
                      if tmp_path else None, **kw)
    try:
        eng.run(max_steps=steps)
        return [[(c.genome.key(), round(c.geomean, 9), c.note)
                 for c in i.lineage.commits] for i in eng.islands], eng
    finally:
        eng.close()


def test_engine_lineages_bit_identical_with_cascade():
    """The tentpole gate: the cascade — promotion off OR on — must reproduce
    a cascade-free engine's lineages exactly (rung-0 scoring goes through
    the island's own backend, so it is pure cache warming; calibration only
    reorders promotion)."""
    base, _ = _fingerprints()
    off, _ = _fingerprints(cascade_eta=2, cascade_promote=False)
    on, eng = _fingerprints(cascade_eta=2)
    assert base == off == on
    totals = eng.cascade_totals()
    assert totals["epochs"] > 0
    assert totals["evals"].get(HLO, 0) > 0             # promotion really ran


def test_engine_pays_rung2_in_parent_under_process_backend():
    """Rung 2 times kernels on the chip, which only the parent may hold: it
    is paid inline in the engine's process whatever the island backend."""
    eng = Archipelago(n_islands=1, suite=FAST_SUITE, migration_interval=2,
                      seed=11, backend="process", check_correctness=False,
                      cascade_eta=2)
    try:
        eng.run(max_steps=2)
        rung2 = eng.cascades["default"].rungs[2]
        assert isinstance(rung2, InlineBackend)
        paid = eng.cascade_totals()["evals"][MEASURED]
        assert paid > 0 and rung2.n_evaluations == paid
    finally:
        eng.close()


def test_engine_cascade_report_and_promote_fractions():
    _, eng = _fingerprints(cascade_eta=2, cascade_slate=6)
    for entry in eng.cascade_log:
        n0, n1, n2 = (entry["evals"][f] for f in FIDELITIES)
        if n1:
            assert n1 <= max(1, n0 // 2)
        if n2:
            assert n2 <= max(1, n1 // 2)
    rep = eng.run(max_steps=0)                         # report-only call
    assert rep.cascade["eta"] == 2
    assert rep.score_caches["default"]["per_fidelity"][PERFMODEL] > 0


def test_cascade_kill_resume_replays_promotion_and_calibration(tmp_path):
    """A killed/resumed calibrated run must make the identical promotion and
    correction decisions an uninterrupted run makes — factors ride in the
    archipelago payload and the slate is a pure function of persisted
    state."""
    kw = dict(cascade_eta=2, cascade_slate=5)
    _, solid = _fingerprints(tmp_path, tag="a", steps=8, **kw)

    eng1 = Archipelago(n_islands=2, suite=FAST_SUITE, migration_interval=2,
                      seed=11, backend="thread", check_correctness=False,
                      persist_path=str(tmp_path / "archb.json"), **kw)
    eng1.run(max_steps=4)
    eng1.close()                                       # "kill"
    eng2 = Archipelago.resume(str(tmp_path / "archb.json"), n_islands=2,
                              suite=FAST_SUITE, migration_interval=2, seed=11,
                              backend="thread", check_correctness=False, **kw)
    try:
        eng2.run(max_steps=4)
        strip = lambda log: [  # noqa: E731
            {k: e[k] for k in ("epoch", "island", "evals", "promoted")}
            for e in log]
        assert strip(eng2.cascade_log) == strip(solid.cascade_log)
        assert eng2.calibration.state() == solid.calibration.state()
        assert [[c.genome.key() for c in i.lineage.commits]
                for i in eng2.islands] == \
               [[c.genome.key() for c in i.lineage.commits]
                for i in solid.islands]
    finally:
        eng2.close()


def test_engine_rejects_bad_cascade_params():
    with pytest.raises(ValueError, match="cascade_eta"):
        Archipelago(n_islands=2, suite=FAST_SUITE, cascade_eta=1)
    with pytest.raises(ValueError, match="cascade_slate"):
        Archipelago(n_islands=2, suite=FAST_SUITE, cascade_eta=2,
                    cascade_slate=0)
