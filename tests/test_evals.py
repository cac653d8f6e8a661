"""The pluggable evaluation service: cache API, backend factory, thread
backend concurrency contract (prefetch dedup, owner-failure retry), process
backend (parent-side cache, in-flight dedup, bit-identity with inline), the
backend lifecycle contract parametrized over EVERY concurrent backend
(thread / process / process-on-elastic-pool / service), the picklable worker
function, the scenario registry, and registry auto-scaling of the
archipelago.  The socket service's own registry/heartbeat/fault paths live
in tests/test_service.py."""
import concurrent.futures as cf
import os
import pickle
import threading

import jax
import pytest

from repro.core import (Archipelago, BatchScorer, InlineBackend, KernelGenome,
                        ProcessBackend, ScoreCache, Scorer, make_backend,
                        register_suite, registered_suites, seed_genome,
                        suite_by_name, unregister_suite)
from repro.core.evals import (ElasticProcessPool, EvalSpec, ThreadBackend,
                              evaluate_genome)
from repro.core.evals.backends import make_process_executor
from repro.core.evals.service import _worker_env
from repro.core.perfmodel import BenchConfig

FAST_SUITE = [BenchConfig("c4k", 8, 16, 16, 4096, causal=True),
              BenchConfig("n4k", 8, 16, 16, 4096, causal=False)]


# -- ScoreCache ----------------------------------------------------------------


def test_score_cache_api():
    cache = ScoreCache()
    assert cache.get("k") is None and cache.misses == 1
    sc = Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache)
    sv = sc(seed_genome())
    key = seed_genome().key()
    assert key in cache and len(cache) == 1
    assert cache.get(key).values == sv.values
    assert cache.hits == 1
    # peek is uncounted
    assert cache.peek(key) is not None and cache.hits == 1
    cache.clear()
    assert key not in cache and len(cache) == 0


def test_scorer_memoizes_through_cache():
    sc = Scorer(suite=FAST_SUITE, check_correctness=False)
    g = seed_genome()
    a, b = sc(g), sc(g)
    assert a.values == b.values
    assert sc.n_evaluations == 1
    assert sc.cache.hits == 1


def test_shared_cache_across_scorers():
    cache = ScoreCache()
    s1 = Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache)
    s2 = Scorer(suite=FAST_SUITE, check_correctness=False, cache=cache)
    s1(seed_genome())
    s2(seed_genome())
    assert s1.n_evaluations == 1 and s2.n_evaluations == 0


# -- backend factory -----------------------------------------------------------


def test_make_backend_names():
    inline = make_backend("inline", suite=FAST_SUITE, check_correctness=False)
    thread = make_backend("thread", suite=FAST_SUITE, check_correctness=False)
    assert isinstance(inline, InlineBackend)
    assert isinstance(thread, ThreadBackend)
    assert ThreadBackend is BatchScorer
    g = seed_genome()
    assert inline(g).values == thread(g).values
    thread.close()
    with pytest.raises(ValueError, match="unknown eval backend"):
        make_backend("gpu")


def test_make_backend_resolves_registered_suite_names():
    b = make_backend("inline", suite="decode", check_correctness=False)
    assert [c.name for c in b.suite] == \
        [c.name for c in suite_by_name("decode")]


def test_inline_backend_surface():
    b = make_backend("inline", suite=FAST_SUITE, check_correctness=False)
    genomes = [seed_genome(), seed_genome().with_(block_q=256), seed_genome()]
    svs = b.map(genomes)
    assert [sv.values for sv in svs] == [b(g).values for g in genomes]
    b.prefetch(genomes)                       # no-op, must not pay
    assert b.n_evaluations == 2
    assert b.cache_hits > 0
    b.close()


def test_inline_submit_is_synchronous_completed_future():
    b = make_backend("inline", suite=FAST_SUITE, check_correctness=False)
    assert b.overlapping is False             # speculation skips this backend
    fut = b.submit(seed_genome())
    assert fut.done()                         # evaluated in the calling thread
    assert fut.result().values == b(seed_genome()).values
    b.close()


def test_backend_worker_width_derived_from_cpu_count():
    """The thread backend's default width comes from os.cpu_count (clamped),
    never a hard-coded constant, and the chosen width is exposed."""
    import os
    b = make_backend("thread", suite=FAST_SUITE, check_correctness=False)
    assert b.max_workers == max(2, min(8, os.cpu_count() or 2))
    b.close()
    b = make_backend("thread", suite=FAST_SUITE, check_correctness=False,
                     max_workers=3)
    assert b.max_workers == 3
    b.close()


# -- thread backend: prefetch dedup + owner-failure retry ----------------------


class _SpyExecutor:
    """Counts submissions on the way to a real executor."""

    def __init__(self, inner):
        self.inner = inner
        self.submitted = 0

    def submit(self, fn, *args, **kw):
        self.submitted += 1
        return self.inner.submit(fn, *args, **kw)

    def shutdown(self, **kw):
        self.inner.shutdown(**kw)


class _GatedScorer(Scorer):
    """Evaluation blocks until the gate opens (concurrency-window control)."""

    def __init__(self, **kw):
        super().__init__(check_correctness=False, **kw)
        self.started = threading.Event()
        self.gate = threading.Event()

    def score_uncached(self, genome):
        self.started.set()
        assert self.gate.wait(10)
        return super().score_uncached(genome)


def test_prefetch_skips_inflight_evaluations():
    spy = _SpyExecutor(cf.ThreadPoolExecutor(2))
    base = _GatedScorer(suite=FAST_SUITE)
    batch = BatchScorer(base, executor=spy)
    g = seed_genome()
    owner = threading.Thread(target=batch, args=(g,))
    owner.start()
    assert base.started.wait(10)               # g is now in flight
    assert batch.in_flight == (g.key(),)
    batch.prefetch([g])                        # in flight -> must not submit
    assert spy.submitted == 0
    base.gate.set()
    owner.join()
    batch.prefetch([g])                        # cached -> must not submit
    assert spy.submitted == 0
    g2 = seed_genome().with_(block_q=256)
    batch.prefetch([g2])                       # genuinely new -> submits
    assert spy.submitted == 1
    batch.close()
    spy.inner.shutdown(wait=True)


class _FlakyScorer(Scorer):
    """First evaluation raises (after a waiter has queued); later ones work."""

    def __init__(self, **kw):
        super().__init__(check_correctness=False, **kw)
        self.calls = 0
        self.first_started = threading.Event()
        self.release_first = threading.Event()

    def score_uncached(self, genome):
        self.calls += 1
        if self.calls == 1:
            self.first_started.set()
            assert self.release_first.wait(10)
            raise RuntimeError("transient evaluator failure")
        return super().score_uncached(genome)


def test_owner_failure_propagates_and_waiter_retries():
    base = _FlakyScorer(suite=FAST_SUITE)
    batch = BatchScorer(base)
    g = seed_genome()
    results = {}

    def call(tag):
        try:
            results[tag] = batch(g)
        except RuntimeError as e:
            results[tag] = e

    t1 = threading.Thread(target=call, args=("owner",))
    t1.start()
    assert base.first_started.wait(10)
    t2 = threading.Thread(target=call, args=("waiter",))
    t2.start()                   # joins the in-flight wait behind the owner
    base.release_first.set()     # owner raises; waiter must wake and retry
    t1.join(10); t2.join(10)

    assert isinstance(results["owner"], RuntimeError)
    assert not isinstance(results["waiter"], Exception)
    assert results["waiter"].values == Scorer(
        suite=FAST_SUITE, check_correctness=False)(g).values
    assert base.calls == 2                       # failed try + waiter's retry
    assert batch.in_flight == ()                 # nothing leaked
    assert batch(g).values == results["waiter"].values   # cached now
    batch.close()


# -- the unified async surface (submit) ----------------------------------------


def test_batch_scorer_call_collapses_onto_submitted_future():
    """The pipelined contract: a proposal-phase submit followed by the
    harvest's synchronous call must pay exactly one evaluation."""
    batch = BatchScorer(Scorer(suite=FAST_SUITE, check_correctness=False))
    g = seed_genome().with_(block_q=256)
    fut = batch.submit(g)
    sv = batch(g)
    assert fut.result(10).values == sv.values
    assert batch.n_evaluations == 1
    batch.close()


# -- backend lifecycle: ONE contract, parametrized over every concurrent
# -- backend (thread, process, process-on-elastic-pool, service) ----------------

LIFECYCLE_BACKENDS = ("thread", "process", "process-elastic", "service")


def _lifecycle_backend(name, service_latency_s=0.0):
    """(backend, finalizers): one small instance of each concurrent backend
    flavour, plus teardown for infrastructure the backend does not own
    (elastic pool, in-process service worker)."""
    from repro.core import ElasticProcessPool
    from repro.core.evals import ServiceBackend
    from repro.core.evals.service_worker import EvalServiceWorker
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False,
                            service_latency_s=service_latency_s)
    if name == "service":
        b = ServiceBackend(spec=spec, workers=0)
        w = EvalServiceWorker(*b.address, slots=1, name="lifecycle")
        t = threading.Thread(target=w.run, daemon=True)
        t.start()
        assert b.coordinator.wait_for_workers(1, timeout=10)
        return b, [w.stop, lambda: t.join(5)]
    if name == "process-elastic":
        pool = ElasticProcessPool(
            slot_factory=lambda: cf.ThreadPoolExecutor(max_workers=1),
            min_workers=1, max_workers=2)
        b = ProcessBackend(spec=spec, executor=pool)
        return b, [lambda: pool.shutdown(wait=True, cancel_futures=True)]
    kw = {"max_workers": 1} if name == "process" else {}
    return make_backend(name, suite=spec, **kw), []


@pytest.mark.parametrize("name", LIFECYCLE_BACKENDS)
def test_backend_close_idempotent_and_submit_after_close_raises(name):
    b, finalizers = _lifecycle_backend(name)
    try:
        assert b(seed_genome()).values         # the backend actually works
        b.close()
        b.close()                              # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            b.submit(seed_genome())
    finally:
        for fin in finalizers:
            fin()


@pytest.mark.parametrize("name", LIFECYCLE_BACKENDS)
def test_backend_inflight_dedup_shares_one_future(name):
    """Submit the same genome twice while its evaluation is in flight (a
    latency-modelled spec holds it open): the SAME future comes back, one
    evaluation is paid, and a post-completion submit is a completed cache
    hit — the contract the pipelined proposal phase leans on, identical on
    every concurrent backend."""
    b, finalizers = _lifecycle_backend(name, service_latency_s=0.4)
    try:
        g = seed_genome()
        f1 = b.submit(g)
        f2 = b.submit(g)                       # in flight -> shared future
        assert f2 is f1
        sv = f1.result(30)
        assert sv.values == Scorer(suite=FAST_SUITE,
                                   check_correctness=False)(g).values
        f3 = b.submit(g)                       # cached -> completed future
        assert f3.done() and f3.result().values == sv.values
        assert b.n_evaluations == 1
    finally:
        b.close()
        for fin in finalizers:
            fin()


# -- the picklable worker ------------------------------------------------------


def test_eval_spec_resolve_and_pickle():
    by_name = EvalSpec.resolve("decode", check_correctness=False)
    assert [c.name for c in by_name.suite] == \
        [c.name for c in suite_by_name("decode")]
    explicit = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    assert explicit is EvalSpec.resolve(explicit)
    clone = pickle.loads(pickle.dumps(explicit))
    assert clone == explicit                     # frozen + hashable round-trip


def test_service_latency_changes_wall_never_values():
    """service_latency_s models a latency-bound evaluation service: paid
    evaluations hold the latency, values stay bit-identical, cache hits pay
    nothing — and the spec carries it to workers."""
    import time
    fast = Scorer(suite=FAST_SUITE, check_correctness=False)
    slow = Scorer(suite=FAST_SUITE, check_correctness=False,
                  service_latency_s=0.1)
    g = seed_genome()
    t0 = time.perf_counter()
    sv = slow(g)
    assert time.perf_counter() - t0 >= 0.1
    assert sv.values == fast(g).values
    t0 = time.perf_counter()
    slow(g)                                    # cached: no latency paid
    assert time.perf_counter() - t0 < 0.1
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False,
                            service_latency_s=0.1)
    t0 = time.perf_counter()
    assert evaluate_genome(g, spec).values == sv.values
    assert time.perf_counter() - t0 >= 0.1


def test_evaluate_genome_matches_scorer():
    g = seed_genome().with_(kv_in_grid=True)
    sv = evaluate_genome(g, "decode", check_correctness=False)
    ref = Scorer(suite=suite_by_name("decode"), check_correctness=False)(g)
    assert sv.values == ref.values
    assert sv.config_names == ref.config_names


# -- process backend -----------------------------------------------------------


def test_process_backend_dedup_and_parent_cache():
    b = make_backend("process", suite=FAST_SUITE, check_correctness=False,
                     max_workers=2)
    try:
        g1, g2 = seed_genome(), seed_genome().with_(block_q=256)
        svs = b.map([g1, g2, g1, g2, g1])       # duplicates share one task
        assert b.n_evaluations == 2
        assert [sv.values for sv in svs[:2]] == \
            [svs[2].values, svs[3].values]
        before = b.n_evaluations
        again = b.map([g1, g2])                 # parent cache: no new tasks
        assert b.n_evaluations == before
        assert b.cache_hits >= 2
        assert [a.values for a in again] == [svs[0].values, svs[1].values]
        assert b.in_flight == ()
    finally:
        b.close()


def test_process_backend_bit_identical_to_inline():
    """The acceptance gate: a fixed genome batch scored by the process
    backend must be bit-identical to the inline path — correctness verdicts,
    per-config TFLOPS, and profile breakdowns."""
    suite = [BenchConfig("c2k", 1, 4, 4, 2048, causal=True)]
    genomes = [seed_genome(),
               seed_genome().with_(block_q=512, kv_in_grid=True),
               seed_genome().with_(mask_mode="block_skip",
                                   rescale_mode="branchless"),
               seed_genome().with_(acc_dtype="bf16")]   # fails correctness
    proc = make_backend("process", suite=suite, max_workers=2)
    try:
        got = proc.map(genomes)
    finally:
        proc.close()
    inline = make_backend("inline", suite=suite)
    want = inline.map(genomes)
    for a, b in zip(got, want):
        assert a.correct == b.correct
        assert a.values == b.values              # bit-identical, no approx
        assert a.config_names == b.config_names
        assert a.failure == b.failure
        assert {n: p.breakdown() for n, p in a.profiles.items()} == \
            {n: p.breakdown() for n, p in b.profiles.items()}
    assert not want[-1].correct                  # the bf16 trap really fired


@pytest.mark.parametrize("pool", ["fixed", "elastic"])
def test_process_workers_hold_jax_to_cpu(pool, monkeypatch):
    """Workers pay rung 0 and the interpreter check only; the chip belongs
    to the parent.  Whatever environment they start from, their jax is
    pinned to the CPU before first use."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    spec = EvalSpec.resolve(FAST_SUITE, False)
    ex = (make_process_executor([spec], max_workers=1) if pool == "fixed"
          else ElasticProcessPool((spec,), max_workers=1))
    try:
        assert ex.submit(os.getenv, "JAX_PLATFORMS").result() == "cpu"
        assert ex.submit(jax.default_backend).result() == "cpu"
    finally:
        ex.shutdown()


def test_service_worker_env_holds_jax_to_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _worker_env()["JAX_PLATFORMS"] == "cpu"


# -- the removed compat shim ---------------------------------------------------


def test_scoring_shim_is_gone():
    """repro.core.scoring (deprecated in PR 5) is deleted; the supported
    import path is repro.core.evals."""
    import importlib
    import sys
    sys.modules.pop("repro.core.scoring", None)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.scoring")


# -- scenario registry ---------------------------------------------------------


def test_registry_enumeration_and_validation():
    assert {"mha", "gqa", "decode"} <= set(registered_suites())
    with pytest.raises(ValueError, match="invalid suite name"):
        register_suite("a+b", lambda: [])
    with pytest.raises(ValueError, match="already registered"):
        register_suite("mha", lambda: [])


def test_register_suite_extends_unions():
    register_suite("tiny", lambda: [BenchConfig("tiny_c", 1, 4, 4, 1024)])
    try:
        assert "tiny" in registered_suites()
        union = suite_by_name("mha+tiny")
        assert union[-1].name == "tiny_c"
    finally:
        unregister_suite("tiny")
    assert "tiny" not in registered_suites()
    with pytest.raises(ValueError, match="unknown suite"):
        suite_by_name("tiny")


def test_from_registry_one_island_per_suite():
    eng = Archipelago.from_registry(check_correctness=False, seed=5)
    try:
        assert sorted(i.name for i in eng.islands) == \
            sorted(registered_suites())
        for isl in eng.islands:
            assert tuple(c.name for c in isl.scorer.suite) == \
                tuple(c.name for c in suite_by_name(isl.name))
    finally:
        eng.close()


def test_registered_suite_becomes_working_island():
    """The second acceptance gate: registering a new scenario family gives a
    working specialist island with zero engine-code change."""
    register_suite("tiny", lambda: [BenchConfig("tiny_c", 1, 4, 4, 1024)])
    try:
        eng = Archipelago.from_registry(suites=["tiny", "decode"],
                                        check_correctness=False, seed=7,
                                        migration_interval=2)
        try:
            rep = eng.run(max_steps=4)
            tiny = next(i for i in eng.islands if i.name == "tiny")
            assert tuple(c.name for c in tiny.scorer.suite) == ("tiny_c",)
            assert len(tiny.lineage) > 0
            assert tiny.best_geomean() > 0
            assert rep.commits > 0
        finally:
            eng.close()
    finally:
        unregister_suite("tiny")


# -- engine x backend ----------------------------------------------------------


def _engine_fingerprints(backend, **kw):
    eng = Archipelago(n_islands=2, suite=FAST_SUITE, migration_interval=2,
                      seed=11, backend=backend, check_correctness=False, **kw)
    try:
        eng.run(max_steps=4)
        return [[(c.genome.key(), round(c.geomean, 9), c.note)
                 for c in i.lineage.commits] for i in eng.islands]
    finally:
        eng.close()


def test_engine_lineages_identical_across_backends():
    """Backend choice is wall-clock only: the search must not notice — not
    even when scoring leaves the host entirely (service backend over two
    localhost socket workers)."""
    assert _engine_fingerprints("thread") == \
        _engine_fingerprints("process") == \
        _engine_fingerprints("inline") == \
        _engine_fingerprints("service", service_workers=2)


def test_engine_lineages_identical_pipelined_and_elastic():
    """The pipelined acceptance gate at the evals layer: propose->submit->
    harvest stepping — on the thread backend AND on a process backend whose
    pool is elastic — commits the same lineages as the barrier engine."""
    base = _engine_fingerprints("thread")
    assert base == _engine_fingerprints("thread", pipeline=True)
    assert base == _engine_fingerprints("process", pipeline=True,
                                        elastic_workers=2)


def test_engine_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown eval backend"):
        Archipelago(n_islands=2, suite=FAST_SUITE, backend="quantum")


def test_engine_rejects_elastic_without_process_backend():
    with pytest.raises(ValueError, match="elastic_workers requires"):
        Archipelago(n_islands=2, suite=FAST_SUITE, backend="thread",
                    elastic_workers=4)
