"""The inline evaluation path: correctness + profiling in the calling process.

Correctness is executed for real: the genome is materialized into its Pallas
kernel and run against the ``ref.py`` oracle on a reduced proxy shape — the
compiled kernel when the process's platform is ``tpu``, the Pallas
interpreter on ``cpu``.  Throughput depends on the scorer's *fidelity* rung
(the evaluation cascade's ladder):

- ``perfmodel`` (rung 0, default): ``perfmodel.estimate`` — see that module's
  docstring for the machine model.  Bit-identical to the pre-cascade scorer.
- ``hlo`` (rung 1): trace the genome's kernel to HLO on the reduced proxy
  shape and score with the roofline three-term model over
  ``HloAnalysis.summary`` totals (compute/memory/collective).
- ``measured`` (rung 2): on a TPU, compile the genome's kernel at each suite
  config's own shape and time it on the device; a config the compiler
  refuses scores 0 with the compiler's message as its reason.  On a CPU-only
  process, the deterministic ``perfmodel.measured_estimate`` modelled timer.
  ``ScoreVector.timer`` names which of the two produced the values.

:class:`Scorer` is a deterministic function of the genome at rungs 0 and 1
and at the modelled rung 2: the proxy inputs are rebuilt from ``rng_seed``
alone, so two scorers with the same suite, seed, and fidelity — in the same
process or different ones — return bit-identical :class:`ScoreVector`\\ s.
The process backend leans on exactly this property.  Device timings vary
from run to run.  :meth:`Scorer.score_key` carries the fidelity into the
cache key (``cache.fidelity_key``) so rungs never alias one another.
"""
from __future__ import annotations

import functools
import itertools
import math
import os
import threading
import time
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from repro.core import obs, perfmodel
from repro.core.evals.cache import (FIDELITIES, HLO, MEASURED, PERFMODEL,
                                    ScoreCache, fidelity_key)
from repro.core.evals.vector import ScoreVector
from repro.core.perfmodel import (KERNEL_LAUNCH, BenchConfig, estimate,
                                  estimate_batch, measured_estimate,
                                  mha_suite, useful_flops)
from repro.core.search_space import KernelGenome

CORRECTNESS_TOL = 2e-5
MODELLED_TIMER = "modelled"
TIMED_REPS = 5

# one chip, one timed kernel at a time: rung-2 evaluations from concurrent
# islands queue here instead of overlapping on the device
_DEVICE_LOCK = threading.Lock()

# ---------------------------------------------------------------------------
# batch-path switch
# ---------------------------------------------------------------------------

# One switch degrades every batched surface (Scorer.score_batch vectorization,
# BatchScorer/ProcessBackend batched dispatch, the service worker's per-frame
# scoring) to the scalar path — both compute bit-identical results (gated by
# the slate smoke), so this exists for A/B gating and emergency rollback, not
# semantics.  Seeded from the environment so spawned service workers inherit
# the parent's setting (service.py propagates REPRO_BATCH_SCORING).
_BATCH_SCORING = os.environ.get("REPRO_BATCH_SCORING", "1") != "0"


def set_batch_scoring(enabled: bool) -> None:
    """Globally enable/disable the columnar slate-scoring path (process-wide;
    already-spawned remote workers keep the setting they inherited)."""
    global _BATCH_SCORING
    _BATCH_SCORING = bool(enabled)


def batch_scoring_enabled() -> bool:
    return _BATCH_SCORING


# ---------------------------------------------------------------------------
# structure-keyed correctness memo
# ---------------------------------------------------------------------------

CHECK_MEMO_CAP = 256


class _CorrectnessMemo:
    """Bounded LRU over *structural* correctness keys.

    The interpreter run in :meth:`Scorer.check` depends only on the genome's
    kernel-structural fields after the proxy block clamp, the proxy shape
    set (the suite's ``(causal, proxy-window)`` pairs + GQA bit), and the
    RNG seed — not on the whole genome.  Micro-variant slates (block sweeps
    that clamp to the same proxy blocks) therefore pay the interpreter once
    per structure.  Process-wide, like the worker scorer LRU it sits beside:
    every Scorer in the process shares it, keys carry the shape signature so
    distinct suites/seeds never alias."""

    def __init__(self, cap: int = CHECK_MEMO_CAP):
        self.cap = cap
        self._lock = threading.Lock()
        self._data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key):
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.hits += 1
                return self._data[key]
            self.misses += 1
            return None

    def put(self, key, value) -> None:
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self.cap:
                self._data.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = 0
            self.misses = 0

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._data), "cap": self.cap}


_CHECK_MEMO = _CorrectnessMemo()


def correctness_memo_stats() -> dict:
    """Hit/miss/size counters of the process-wide correctness memo (surfaced
    through ``Toolbelt.stats``; remote workers each hold their own memo)."""
    return _CHECK_MEMO.stats()

# proxy geometry shared by the correctness check and the hlo/measured rungs:
# small enough for the interpreter, big enough that blocks/windows survive
PROXY_SEQ = 160


def _proxy_window(window: Optional[int], ref_seq: int) -> Optional[int]:
    """Scale a suite config's window onto the proxy sequence length.

    The proxy runs at ``PROXY_SEQ`` tokens, so a window is rescaled in
    proportion to the config's own sequence length, clamped so it stays a
    *partial* window on the proxy (floor 16 = one block row; ceiling
    ``PROXY_SEQ - 32`` keeps some tokens masked).  Two suites with distinct
    window sets now map to distinct proxy shapes instead of both collapsing
    to w=48."""
    if window is None:
        return None
    ref_seq = max(int(ref_seq), 1)
    return max(16, min(PROXY_SEQ - 32, round(window * PROXY_SEQ / ref_seq)))


def _correctness_proxy_shapes(suite: Sequence[BenchConfig]):
    """Small executable shapes covering the mask/window/GQA space of the
    suite.  One shape per distinct ``(causal, proxy window)`` pair, with the
    proxy window derived from the configs that use that window (largest
    sequence length among them anchors the rescale)."""
    shapes = []
    seen = set()
    has_gqa = any(c.n_heads != c.n_kv_heads for c in suite)
    for causal in sorted({c.causal for c in suite}):
        windows = sorted({c.window for c in suite}, key=lambda w: (w is None, w))
        for window in windows:
            ref_seq = max((c.seq_len for c in suite if c.window == window),
                          default=PROXY_SEQ)
            w = _proxy_window(window, ref_seq)
            if (causal, w) in seen:
                continue
            seen.add((causal, w))
            shapes.append(dict(B=1, Hq=4, Hkv=(2 if has_gqa else 4),
                               S=PROXY_SEQ, D=64, causal=causal, window=w))
    return shapes


class Scorer:
    """Callable scoring function with per-genome memoization.

    The memo lives in ``self.cache`` (a :class:`ScoreCache`); pass one in to
    share it, or read it afterwards — never reach into scorer privates.
    """

    def __init__(self, suite: Optional[Sequence[BenchConfig]] = None,
                 check_correctness: bool = True, rng_seed: int = 0,
                 cache: Optional[ScoreCache] = None,
                 service_latency_s: float = 0.0,
                 fidelity: str = PERFMODEL):
        """``service_latency_s`` > 0 holds every *paid* evaluation for that
        long before scoring — modelling a latency-bound evaluation service
        (cross-host scoring, hardware in the loop; the paper's f is a GPU
        verification run the agent keeps proposing against).  The sleep
        costs no CPU and never changes values, so backends stay
        bit-identical; benchmarks use it to isolate stepping-strategy
        overlap from host CPU capacity.

        ``fidelity`` selects the throughput rung (see the module docstring);
        it flows into :meth:`score_key` so a shared :class:`ScoreCache`
        holds each rung's scores under distinct keys."""
        if fidelity not in FIDELITIES:
            raise ValueError(f"unknown fidelity {fidelity!r}; "
                             f"known: {FIDELITIES}")
        self.suite = list(suite) if suite is not None else mha_suite()
        self.check_correctness = check_correctness
        self.rng_seed = rng_seed
        self.service_latency_s = service_latency_s
        self.fidelity = fidelity
        self.cache = cache if cache is not None else ScoreCache()
        # paid-eval counter: itertools.count().__next__ is GIL-atomic, so
        # concurrent backends count without a lock (read via n_evaluations)
        self._eval_count = itertools.count()
        self._proxy_lock = threading.Lock()
        self._proxy_inputs = None
        self._shape_sig = None

    @property
    def n_evaluations(self) -> int:
        """Paid (uncached) evaluations so far.  ``repr(count)`` exposes the
        next value without consuming it — a lock-free read of a lock-free
        counter."""
        r = repr(self._eval_count)
        return int(r[r.index("(") + 1:-1])

    # -- correctness ----------------------------------------------------------
    def warm(self) -> None:
        """Build the RNG-derived proxy inputs eagerly — a no-op once built.
        The lazy build itself is lock-protected, so this is purely a
        prewarmer: worker initializers call it so the first real evaluation
        is not penalized."""
        if self.check_correctness:
            self._proxy_data()

    def _proxy_data(self):
        if self._proxy_inputs is None:
            with self._proxy_lock:
                if self._proxy_inputs is not None:    # lost the build race
                    return self._proxy_inputs
                import jax.numpy as jnp
                rng = np.random.default_rng(self.rng_seed)
                shapes = _correctness_proxy_shapes(self.suite)
                data = []
                for sh in shapes:
                    q = jnp.asarray(rng.normal(size=(sh["B"], sh["Hq"], sh["S"], sh["D"])),
                                    jnp.float32)
                    k = jnp.asarray(rng.normal(size=(sh["B"], sh["Hkv"], sh["S"], sh["D"])),
                                    jnp.float32)
                    v = jnp.asarray(rng.normal(size=(sh["B"], sh["Hkv"], sh["S"], sh["D"])),
                                    jnp.float32)
                    data.append((sh, q, k, v))
                self._proxy_inputs = data
        return self._proxy_inputs

    @staticmethod
    def _clamped_kwargs(genome: KernelGenome) -> dict:
        """Kernel kwargs with blocks scaled down onto the proxy shapes, so
        the structural path (grid/loop/skip/branch) is still exercised."""
        kw = genome.kernel_kwargs()
        kw["block_q"] = max(16, min(kw["block_q"], 2048) // 16)
        kw["block_k"] = max(16, min(kw["block_k"], 2048) // 16)
        return kw

    def structural_key(self, genome: KernelGenome) -> tuple:
        """The correctness-memo key: everything the interpreter run actually
        depends on.  Clamped kernel kwargs (micro-variants whose blocks clamp
        to the same proxy blocks collide — the memo's whole point) plus the
        proxy-shape signature (the suite's ``(causal, proxy window)`` set +
        GQA bit) and the input seed, so distinct suites/seeds never alias."""
        if self._shape_sig is None:
            self._shape_sig = tuple(sorted(
                (sh["B"], sh["Hq"], sh["Hkv"], sh["S"], sh["D"], sh["causal"],
                 -1 if sh["window"] is None else sh["window"])
                for sh in _correctness_proxy_shapes(self.suite)))
        kw = self._clamped_kwargs(genome)
        return (self._shape_sig, self.rng_seed,
                tuple(sorted(kw.items())))

    def check(self, genome: KernelGenome) -> tuple[bool, str]:
        """Execute the genome's kernel against the oracle (compiled on a
        TPU, interpreted on CPU) — memoized per kernel structure in the
        process-wide bounded LRU."""
        key = self.structural_key(genome)
        cached = _CHECK_MEMO.get(key)
        if cached is not None:
            return cached
        result = self._check_uncached(genome)
        _CHECK_MEMO.put(key, result)
        return result

    def _check_uncached(self, genome: KernelGenome) -> tuple[bool, str]:
        import jax
        import jax.numpy as jnp
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ref import mha_reference
        kw = self._clamped_kwargs(genome)
        interpret = jax.default_backend() == "cpu"
        for sh, q, k, v in self._proxy_data():
            try:
                o = flash_attention(q, k, v, causal=sh["causal"], window=sh["window"],
                                    interpret=interpret, **kw)
            except Exception as e:  # trace/lowering failure
                return False, f"kernel raised: {type(e).__name__}: {e}"
            # full f32 matmuls: a TPU's default precision rounds f32 to bf16
            with jax.default_matmul_precision("highest"):
                r = mha_reference(q, k, v, causal=sh["causal"], window=sh["window"])
            err = float(jnp.max(jnp.abs(o - r)))
            if not math.isfinite(err) or err > CORRECTNESS_TOL:
                return False, (f"numerical mismatch vs oracle: max|err|={err:.2e} "
                               f"on {sh}")
        return True, ""

    # -- scoring ----------------------------------------------------------------
    def score_key(self, genome: KernelGenome) -> str:
        """The cache/dedup key for this genome *at this scorer's fidelity*.
        Backends key their caches, in-flight tables, and futures with this so
        a genome scored at rung 0 re-scores (never aliases) at rung 2."""
        return fidelity_key(genome.key(), self.fidelity)

    def __call__(self, genome: KernelGenome) -> ScoreVector:
        key = self.score_key(genome)
        sv = self.cache.get(key)
        if sv is not None:
            return sv
        sv = self.score_uncached(genome)
        self.cache.put(key, sv)
        return sv

    def score_uncached(self, genome: KernelGenome) -> ScoreVector:
        """Pay the full evaluation cost, bypassing the memo cache (concurrent
        backends manage the cache themselves and call this directly)."""
        t0 = time.perf_counter()
        try:
            return self._score_uncached_inner(genome)
        finally:
            dur = time.perf_counter() - t0
            self.cache.record_eval_seconds(self.fidelity, dur)
            if obs.enabled():
                # the lifecycle "score" span: inline scoring inherits the
                # harvest walk's thread-local trace; thread-backend chunks
                # run under the submitting thread's trace (re-bound by
                # BatchScorer); service workers measure their own spans
                obs.span("score", obs.current_trace(), dur_s=dur,
                         rung=self.fidelity, n=1)

    def _score_uncached_inner(self, genome: KernelGenome) -> ScoreVector:
        next(self._eval_count)
        if self.service_latency_s > 0:
            time.sleep(self.service_latency_s)

        if self.check_correctness:
            ok, why = self.check(genome)
            if not ok:
                return ScoreVector(tuple(c.name for c in self.suite),
                                   tuple(0.0 for _ in self.suite), False,
                                   why)

        timer = ""
        if self.fidelity == HLO:
            values, profiles = self._hlo_values(genome)
        elif self.fidelity == MEASURED:
            values, profiles, timer = self._measured_values(genome)
        else:
            values, profiles = [], {}
            for cfg in self.suite:
                p = estimate(genome, cfg)
                profiles[cfg.name] = p
                values.append(p.tflops if p.feasible else 0.0)
        return self._assemble(values, profiles, timer)

    def score_batch(self, genomes: Sequence[KernelGenome]) -> list[ScoreVector]:
        """Batched :meth:`score_uncached`: pay the evaluation cost for every
        entry (no cache, no dedup — backends own both) with one vectorized
        rung-0 model call for the whole slate and one structural-memo lookup
        per genome.  Results are bit-identical to the scalar path; with the
        batch path disabled this *is* the scalar path.  The modelled service
        latency is held once per batch — batching a slate amortizes the
        round trip, which is the point."""
        genomes = list(genomes)
        if not genomes:
            return []
        if not _BATCH_SCORING:
            return [self.score_uncached(g) for g in genomes]
        t0 = time.perf_counter()
        try:
            for _ in genomes:
                next(self._eval_count)
            if self.service_latency_s > 0:
                time.sleep(self.service_latency_s)

            checks = ([self.check(g) for g in genomes]
                      if self.check_correctness
                      else [(True, "")] * len(genomes))
            out: list = [None] * len(genomes)
            todo = [i for i, (ok, why) in enumerate(checks) if ok]
            for i, (ok, why) in enumerate(checks):
                if not ok:
                    out[i] = ScoreVector(tuple(c.name for c in self.suite),
                                         tuple(0.0 for _ in self.suite),
                                         False, why)
            if self.fidelity == PERFMODEL:
                be = estimate_batch([genomes[i] for i in todo], self.suite)
                for k, i in enumerate(todo):
                    profiles = be.profiles(k)
                    values = [profiles[c.name].tflops
                              if profiles[c.name].feasible else 0.0
                              for c in self.suite]
                    out[i] = self._assemble(values, profiles)
            else:                     # hlo/measured stay scalar per genome
                for i in todo:
                    timer = ""
                    if self.fidelity == HLO:
                        values, profiles = self._hlo_values(genomes[i])
                    else:
                        values, profiles, timer = self._measured_values(
                            genomes[i])
                    out[i] = self._assemble(values, profiles, timer)
            return out
        finally:
            dur = time.perf_counter() - t0
            self.cache.record_eval_seconds(self.fidelity, dur)
            if obs.enabled():
                obs.span("score", obs.current_trace(), dur_s=dur,
                         rung=self.fidelity, n=len(genomes))

    def _assemble(self, values, profiles, timer: str = "") -> ScoreVector:
        """The common ScoreVector assembly of both scoring paths (identical
        failure-string derivation, so batch == scalar bit-for-bit)."""
        failure = ""
        if any(v == 0.0 for v in values):
            bad = [c.name for c, v in zip(self.suite, values) if v == 0.0]
            failure = "infeasible on: " + ", ".join(
                f"{n} ({profiles[n].infeasible_reason})" if n in profiles
                else n for n in bad)
        return ScoreVector(tuple(c.name for c in self.suite), tuple(values),
                           True, failure, profiles, timer)

    # -- higher-fidelity rungs -------------------------------------------------
    def _proxy_trace_groups(self):
        """Suite configs grouped by the proxy shape they trace at.  The proxy
        varies only in the mask (causal × rescaled window) — batch/heads/seq
        are fixed small — so a suite's N configs usually need just one or two
        traces per genome."""
        has_gqa = any(c.n_heads != c.n_kv_heads for c in self.suite)
        groups: dict = {}
        for cfg in self.suite:
            ref_seq = max(c.seq_len for c in self.suite if c.window == cfg.window)
            w = _proxy_window(cfg.window, ref_seq)
            key = (cfg.causal, w)
            groups.setdefault(key, []).append(cfg)
        return has_gqa, groups

    def _trace_hlo_summary(self, genome: KernelGenome, causal: bool,
                           window: Optional[int], has_gqa: bool) -> dict:
        """Lower the genome's kernel (interpret mode, proxy shape) to HLO and
        return ``HloAnalysis(...).summary()``.  Abstract tracing only — no
        arrays are materialized and nothing executes."""
        import jax
        import jax.numpy as jnp

        from repro.kernels.flash_attention import flash_attention
        from repro.launch.hlo_analysis import HloAnalysis
        kw = genome.kernel_kwargs()
        kw["block_q"] = max(16, min(kw["block_q"], 2048) // 16)
        kw["block_k"] = max(16, min(kw["block_k"], 2048) // 16)
        hq, hkv = 4, (2 if has_gqa else 4)
        q = jax.ShapeDtypeStruct((1, hq, PROXY_SEQ, 64), jnp.float32)
        k = jax.ShapeDtypeStruct((1, hkv, PROXY_SEQ, 64), jnp.float32)
        v = jax.ShapeDtypeStruct((1, hkv, PROXY_SEQ, 64), jnp.float32)
        fn = functools.partial(flash_attention, causal=causal, window=window,
                               interpret=True, **kw)
        compiled = jax.jit(fn).lower(q, k, v).compile()
        return HloAnalysis(compiled.as_text()).summary()

    @staticmethod
    def roofline_tflops(summary: dict) -> float:
        """The rung-1 score formula: achieved TFLOP/s of the traced kernel
        under the roofline three-term model — HLO flops over the binding
        term (compute vs memory vs collective) plus launch overhead.  A
        staticmethod so tests can assert rung-1 values agree with
        ``HloAnalysis.summary`` totals without re-tracing."""
        from repro.launch.hlo_analysis import roofline_terms
        t = max(roofline_terms(summary).values())
        return summary.get("flops", 0) / (t + KERNEL_LAUNCH) / 1e12

    def _hlo_values(self, genome: KernelGenome):
        """Rung 1: one HLO trace per distinct proxy mask shape; every config
        sharing that shape shares the roofline score.  Perfmodel feasibility
        still gates each config (an over-VMEM genome scores 0.0 on that
        config at every rung)."""
        has_gqa, groups = self._proxy_trace_groups()
        by_name: dict[str, float] = {}
        profiles: dict = {}
        for (causal, window), cfgs in sorted(
                groups.items(), key=lambda kv: (kv[0][0], kv[0][1] is None,
                                                kv[0][1] or 0)):
            try:
                summary = self._trace_hlo_summary(genome, causal, window,
                                                  has_gqa)
                value = self.roofline_tflops(summary)
            except Exception:        # trace/lowering failure -> rung-1 zero
                value = 0.0
            for cfg in cfgs:
                p = estimate(genome, cfg)
                profiles[cfg.name] = p
                by_name[cfg.name] = value if p.feasible else 0.0
        return [by_name[c.name] for c in self.suite], profiles

    def _measured_values(self, genome: KernelGenome):
        """Rung 2: device timing when this process runs on a TPU; on a
        CPU-only process the deterministic ``perfmodel.measured_estimate``
        modelled timer (CI, the process/service workers), named as such in
        the returned timer so it is never mistaken for a measurement."""
        import jax
        if jax.default_backend() == "tpu":
            return self._timed_values(genome)
        values, profiles = [], {}
        for cfg in self.suite:
            p = measured_estimate(genome, cfg)
            profiles[cfg.name] = p
            values.append(p.tflops if p.feasible else 0.0)
        return values, profiles, MODELLED_TIMER

    def _timed_values(self, genome: KernelGenome):
        """Time the compiled kernel on the device at every suite config's
        own shape: bf16, the genome's own blocks, a warm-up call, then
        ``TIMED_REPS`` calls each ended by ``block_until_ready`` (median).
        TFLOP/s is ``perfmodel.useful_flops(cfg)`` over that time — the same
        work count whatever implements it.  A config the compiler refuses
        scores 0 with the compiler's message as its infeasible reason.  The
        profile keeps the model's terms (the calibration's bottleneck
        classes); its ``tflops`` and ``total_s`` are the measurement."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from repro.kernels.flash_attention import flash_attention
        kw = genome.kernel_kwargs()
        values, profiles = [], {}
        with _DEVICE_LOCK:
            for cfg in self.suite:
                p = estimate(genome, cfg)
                kq, kk, kv = jax.random.split(jax.random.key(self.rng_seed), 3)
                shape_q = (cfg.batch, cfg.n_heads, cfg.seq_len, cfg.head_dim)
                shape_kv = (cfg.batch, cfg.n_kv_heads, cfg.seq_len,
                            cfg.head_dim)
                q = jax.random.normal(kq, shape_q, jnp.bfloat16)
                k = jax.random.normal(kk, shape_kv, jnp.bfloat16)
                v = jax.random.normal(kv, shape_kv, jnp.bfloat16)
                run = functools.partial(flash_attention, causal=cfg.causal,
                                        window=cfg.window, **kw)
                try:
                    run(q, k, v).block_until_ready()           # compile + warm-up
                except Exception as e:  # the compiler's refusal is the reason
                    msg = str(e).strip().splitlines()
                    profiles[cfg.name] = dataclasses.replace(
                        p, tflops=0.0, feasible=False,
                        infeasible_reason=(f"compile: {type(e).__name__}: "
                                           f"{msg[0][:200] if msg else ''}"))
                    values.append(0.0)
                    continue
                times = []
                for _ in range(TIMED_REPS):
                    t0 = time.perf_counter()
                    run(q, k, v).block_until_ready()
                    times.append(time.perf_counter() - t0)
                dt = float(np.median(times))
                tflops = useful_flops(cfg) / dt / 1e12
                profiles[cfg.name] = dataclasses.replace(
                    p, tflops=tflops, total_s=dt, feasible=True,
                    infeasible_reason="")
                values.append(tflops)
        return values, profiles, jax.devices()[0].device_kind

    def baselines(self) -> dict:
        """Expert (cuDNN-analogue) and FA-reference scores on this suite."""
        return {
            "expert": tuple(perfmodel.expert_reference(c) for c in self.suite),
            "fa_reference": tuple(perfmodel.fa_reference(c) for c in self.suite),
        }


class InlineBackend(Scorer):
    """The ``inline`` evaluation backend: everything in the calling thread.

    Identical to :class:`Scorer` plus the uniform backend surface
    (``map``/``submit``/``prefetch``/``close``), so callers can hold any
    backend without feature-testing.  ``overlapping`` is False: ``submit``
    evaluates synchronously, so speculative proposal/prefetch phases skip
    this backend — there is no spare capacity to overlap with.
    """

    overlapping = False
    max_workers = 1

    @property
    def cache_hits(self) -> int:
        return self.cache.hits

    def map(self, genomes: Sequence[KernelGenome]) -> list[ScoreVector]:
        return [self(g) for g in genomes]

    def submit(self, genome: KernelGenome):
        """Uniform async surface: evaluate NOW, return a completed future
        (exceptions are captured on the future, like a real executor's)."""
        import concurrent.futures
        fut: concurrent.futures.Future = concurrent.futures.Future()
        try:
            fut.set_result(self(genome))
        except Exception as e:          # pragma: no cover - scorer rarely raises
            fut.set_exception(e)
        return fut

    def prefetch(self, genomes: Sequence[KernelGenome]) -> None:
        """No-op: inline evaluation has no spare capacity to warm with."""

    def close(self) -> None:
        pass
