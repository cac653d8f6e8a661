"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: device, kernels, search, serve
    python chip_smoke.py --chips 4     # four chips: qwen2-7b at full depth, sharded

One chip, four phases, all in this one process (a chip belongs to one
process at a time):

  device   platform, device kind and count; anything but a TPU exits non-zero
  kernels  the compiled Pallas flash attention on every ``mha_suite()`` config
           (16 heads, D=128, bf16, s4096-32768) with the search's seed genome
           and with the genome the models serve with, one GQA config with
           ``gqa_pack``, and the decode kernel at qwen2-7b's decode widths, each
           against ``kernels/ref.py``
  search   a short island evolution on the MHA suite whose cascade pays rung 2
           (kernel timing) on this chip
  serve    ``BatchedServer`` on qwen2-7b at its published widths, depth cut to
           what one chip's HBM holds, through the Pallas kernels; prefill
           logits and greedy tokens checked against the blocked jnp attention

With ``--chips 4`` only the sharded serve phase runs: qwen2-7b at all 28
layers, weights created sharded over a (data=1, model=4) mesh.

Every input is made from ``--seed``.  Informative lines (labelled with the
device) come first; the numbers also go to ``results/chip_smoke*.json``.  The
last line of stdout is ``{"ok": true, "device": {...}}``.  A failed check
raises, and the process exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.qwen2_7b import CONFIG as QWEN2  # noqa: E402
from repro.core import IslandEvolution, seed_genome  # noqa: E402
from repro.core.config import EngineConfig, EvalConfig, MigrationConfig  # noqa: E402
from repro.core.evals import MEASURED, fidelity_key  # noqa: E402
from repro.core.perfmodel import BenchConfig, mha_suite, useful_flops  # noqa: E402
from repro.core.search_space import KernelGenome  # noqa: E402
from repro.distributed.context import set_mesh  # noqa: E402
from repro.distributed.sharding import param_shardings  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.flash_decode import flash_decode  # noqa: E402
from repro.kernels.ops import DEFAULT_ATTN_GENOME  # noqa: E402
from repro.kernels.ref import decode_reference, flash_reference_blocked  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import BatchedServer, Request, make_serve_step  # noqa: E402
from repro.models import init_params  # noqa: E402

# bf16 kernel outputs against an f32 reference: one bf16 rounding of the
# output (2^-8 relative) plus the kernel's own f32 arithmetic
KERNEL_ATOL = KERNEL_RTOL = 1e-2
# Pallas against blocked attention through a bf16 model: the two round
# differently at every layer, so logits agree to this share of the
# reference's logit range, and a served token must be within that margin of
# the reference's best (near-ties flip; 2 layers on CPU differ by 0.3%)
LOGIT_RTOL = 0.05
TIMED_REPS = 5
# the weights may take this share of one chip's HBM; the rest holds the KV
# caches, activations and the blocked reference's temporaries
WEIGHT_SHARE_OF_HBM = 0.8
IMPL = "pallas"
REF_IMPL = "blocked"


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def timed(fn, *args, reps: int = TIMED_REPS):
    """(output, seconds of the first call — compile included, median
    seconds of ``reps`` further calls), every call ended by
    ``block_until_ready``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, first, float(np.median(times))


def phase_device(n_chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    _log("device", f"platform={d.platform} kind={d.device_kind} "
                   f"count={len(devices)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform}")
    if len(devices) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} devices, "
                         f"JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _kernel_cases() -> list[tuple[BenchConfig, str, dict]]:
    genomes = {"seed": seed_genome().kernel_kwargs(),
               "serve": dict(DEFAULT_ATTN_GENOME)}
    cases = [(cfg, name, kw) for cfg in mha_suite()
             for name, kw in genomes.items()]
    gqa = BenchConfig("gqa8_causal_s4096", 8, 32, 4, 4096, causal=True)
    cases.append((gqa, "serve+gqa_pack",
                  dict(DEFAULT_ATTN_GENOME, gqa_pack=True)))
    return cases


def phase_kernels(seed: int, kind: str) -> list[dict]:
    rows, bad = [], []
    by_cfg: dict = {}
    for cfg, name, kw in _kernel_cases():
        by_cfg.setdefault(cfg, []).append((name, kw))
    key = jax.random.key(seed)
    for cfg, runs in by_cfg.items():
        key, kq, kk, kv = jax.random.split(key, 4)
        q = jax.random.normal(kq, (cfg.batch, cfg.n_heads, cfg.seq_len,
                                   cfg.head_dim), jnp.bfloat16)
        k = jax.random.normal(kk, (cfg.batch, cfg.n_kv_heads, cfg.seq_len,
                                   cfg.head_dim), jnp.bfloat16)
        v = jax.random.normal(kv, k.shape, jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            ref = flash_reference_blocked(q, k, v, causal=cfg.causal,
                                          window=cfg.window, block_k=512)
            ref = jax.block_until_ready(ref.astype(jnp.float32))
        for name, kw in runs:
            fn = functools.partial(flash_attention, causal=cfg.causal,
                                   window=cfg.window, **kw)
            out, first, t = timed(fn, q, k, v)
            out = out.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(out - ref)))
            ok = bool(jnp.all(jnp.abs(out - ref)
                              <= KERNEL_ATOL + KERNEL_RTOL * jnp.abs(ref)))
            row = {"config": cfg.name, "genome": name, "first_call_s": first,
                   "time_s": t, "tflops": useful_flops(cfg) / t / 1e12,
                   "max_abs_err": err, "ok": ok, "device": kind}
            rows.append(row)
            _log("kernels", f"{cfg.name:20s} {name:15s} first call "
                            f"{first:7.3f} s | {t * 1e3:9.3f} ms on {kind} | "
                            f"{row['tflops']:7.2f} TFLOP/s | max|err| "
                            f"{err:.2e} {'ok' if ok else 'MISMATCH'}")
            if not ok:
                bad.append(f"{cfg.name}/{name}")
        del q, k, v, ref

    # decode at qwen2-7b's widths against a ragged 8k cache
    B, L = 8, 8192
    Hq, Hkv, D = QWEN2.n_heads, QWEN2.n_kv_heads, QWEN2.head_dim
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, D), jnp.bfloat16)
    kc = jax.random.normal(kk, (B, Hkv, L, D), jnp.bfloat16)
    vc = jax.random.normal(kv, (B, Hkv, L, D), jnp.bfloat16)
    lens = np.random.default_rng(seed).integers(1, L + 1, size=B)
    lens[0], lens[1] = L, 1
    valid = jnp.asarray(lens, jnp.int32)
    out, first, t = timed(flash_decode, q, kc, vc, valid)
    with jax.default_matmul_precision("highest"):
        ref = decode_reference(q, kc, vc, valid).astype(jnp.float32)
    out = out.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    ok = bool(jnp.all(jnp.abs(out - ref)
                      <= KERNEL_ATOL + KERNEL_RTOL * jnp.abs(ref)))
    cache_bytes = 2 * B * Hkv * L * D * 2
    rows.append({"config": f"qwen2_decode_b{B}_l{L}", "genome": "decode",
                 "first_call_s": first, "time_s": t,
                 "cache_gbps": cache_bytes / t / 1e9, "valid_len": lens.tolist(),
                 "max_abs_err": err, "ok": ok, "device": kind})
    _log("kernels", f"{'qwen2_decode 28q/4kv':20s} {'L=8192 ragged':15s} "
                    f"first call {first:7.3f} s | {t * 1e3:9.3f} ms on {kind} "
                    f"| {cache_bytes / t / 1e9:7.1f} GB/s of cache | max|err| "
                    f"{err:.2e} {'ok' if ok else 'MISMATCH'}")
    if not ok:
        bad.append("qwen2_decode")
    if bad:
        raise AssertionError(f"kernel outputs disagree with ref.py: {bad}")
    return rows


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _genome_label(g: KernelGenome) -> str:
    diff = g.diff(seed_genome())
    return "seed" if not diff else ",".join(
        f"{k}={mine}" for k, (mine, _seed) in sorted(diff.items()))


def phase_search(seed: int, kind: str) -> dict:
    config = EngineConfig(
        n_islands=1, suite=mha_suite(), seed=seed,
        evals=EvalConfig(backend="thread", cascade_eta=3, cascade_slate=5),
        migration=MigrationConfig(interval=2))
    t0 = time.perf_counter()
    engine = IslandEvolution(config=config)
    try:
        report = engine.run(max_steps=4)
        cascade = engine.cascades["default"]
        seed_sv = cascade.rungs[2](seed_genome())
        promoted = [k for entry in engine.cascade_log
                    for k in entry["promoted"][MEASURED]]
        paid = engine.cascade_totals().get("evals", {})
        measured = {}
        for gkey in dict.fromkeys(promoted + [seed_genome().key()]):
            measured[gkey] = cascade.cache.peek(fidelity_key(gkey, MEASURED))
    finally:
        engine.close()
    wall = time.perf_counter() - t0
    _log("search", f"{report.commits} commits in {report.steps} steps, "
                   f"{wall:.1f} s wall; paid evaluations per rung {paid}")
    rows = []
    for gkey, sv in measured.items():
        label = _genome_label(KernelGenome.from_dict(json.loads(gkey)))
        vals = " ".join(f"{n.removeprefix('mha_')}={v:.1f}"
                        for n, v in zip(sv.config_names, sv.values))
        _log("search", f"rung 2 [{label}] timer={sv.timer} TFLOP/s: {vals}")
        if sv.failure:
            _log("search", f"rung 2 [{label}] {sv.failure}")
        rows.append({"genome": label, "timer": sv.timer,
                     "values": dict(zip(sv.config_names, sv.values)),
                     "failure": sv.failure})
    if not paid.get(MEASURED):
        raise AssertionError("the cascade paid no rung-2 evaluation")
    timers = {sv.timer for sv in measured.values()}
    if timers != {kind}:
        raise AssertionError(f"rung-2 values from timers {timers}, not the "
                             f"device ({kind})")
    if not seed_sv.correct or min(seed_sv.values) <= 0.0:
        raise AssertionError(f"seed genome scored {seed_sv.values} at rung 2 "
                             f"({seed_sv.failure})")
    return {"commits": report.commits, "steps": report.steps,
            "wall_s": wall, "paid": paid, "rung2": rows}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def random_weights(cfg, seed: int, shardings=None):
    """bf16 weights with ``init_params``' structure and scales, made on the
    device leaf by leaf (an f32 init of qwen2-7b would not fit one chip):
    N(0, 0.02) matrices, norms at one, biases at zero.  ``shardings`` places
    each leaf where it is made, so no device ever holds the whole model."""
    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build():
        out = []
        keys = jax.random.split(jax.random.key(seed), len(leaves))
        for k, (path, s) in zip(keys, leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.endswith("norm"):
                out.append(jnp.ones(s.shape, jnp.bfloat16))
            elif name in ("bq", "bk", "bv"):
                out.append(jnp.zeros(s.shape, jnp.bfloat16))
            else:
                out.append(0.02 * jax.random.normal(k, s.shape, jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)()


def depth_for_one_chip(cfg, hbm_bytes: int) -> int:
    """The most layers whose bf16 weights fit ``WEIGHT_SHARE_OF_HBM``."""
    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    per_layer = sum(2 * x.size // cfg.n_periods
                    for x in jax.tree_util.tree_leaves(shapes["dec"]))
    fixed = sum(2 * x.size for x in jax.tree_util.tree_leaves(
        {name: tree for name, tree in shapes.items() if name != "dec"}))
    return min(cfg.n_layers,
               int((WEIGHT_SHARE_OF_HBM * hbm_bytes - fixed) // per_layer))


def _requests(seed: int, vocab: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(128, 1025, size=4)
    lens[0] = 1024
    return [Request(i, rng.integers(0, vocab, (int(n),)).astype(np.int32),
                    max_new_tokens=16) for i, n in enumerate(lens)]


def serve_and_check(cfg, params, seed: int, kind: str, phase: str) -> dict:
    """Serve four requests through the Pallas kernels; check the prefill
    logits against blocked attention on the same weights, and each served
    token of every request against the blocked model fed the same tokens
    (teacher forcing): it must be the reference's best token or within
    ``LOGIT_RTOL`` of the logit range of it."""
    reqs = _requests(seed, cfg.vocab_size)
    max_len = max(len(r.prompt) for r in reqs) + 16
    server = BatchedServer(cfg, params, batch_size=4, max_len=max_len,
                           compute_dtype=jnp.bfloat16, impl=IMPL)
    t0 = time.perf_counter()
    server.run(reqs)
    serve_s = time.perf_counter() - t0
    n_new = sum(len(r.output) for r in reqs)
    _log(phase, f"{len(reqs)} requests (prompts {[len(r.prompt) for r in reqs]}"
                f") -> {n_new} tokens in {serve_s:.1f} s on {kind}, "
                f"compiles included")
    _log(phase, f"request 0 tokens: {reqs[0].output}")

    logits, _ = server.prefill(reqs)
    ref_server = BatchedServer(cfg, params, batch_size=4, max_len=max_len,
                               compute_dtype=jnp.bfloat16, impl=REF_IMPL)
    ref_logits, ref_cache = ref_server.prefill(reqs)
    diff = float(jnp.max(jnp.abs(logits - ref_logits)))
    spread = float(jnp.max(ref_logits) - jnp.min(ref_logits))
    tol = LOGIT_RTOL * spread
    _log(phase, f"prefill logits {IMPL} vs {REF_IMPL}: max|diff| {diff:.4f} "
                f"(logit range {spread:.2f}, tolerance {tol:.4f})")
    if not diff <= tol:
        raise AssertionError(f"prefill logits differ by {diff} > {tol}")

    ref_step = jax.jit(make_serve_step(cfg, jnp.bfloat16, impl=REF_IMPL))
    exact, worst = 0, 0.0
    for t in range(16):
        served = jnp.asarray([r.output[t] for r in reqs], jnp.int32)
        best = jnp.max(ref_logits, axis=-1)
        gap = best - jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        exact += int(jnp.sum(served == jnp.argmax(ref_logits, -1)))
        if not float(jnp.max(gap)) <= tol:
            raise AssertionError(f"step {t}: a served token is {float(jnp.max(gap))}"
                                 f" below the reference's best logit")
        if t < 15:
            ref_logits, ref_cache = ref_step(params, ref_cache, served)
    _log(phase, f"greedy tokens: {exact}/{16 * len(reqs)} equal the "
                f"{REF_IMPL} reference's argmax; the largest gap to its best "
                f"logit is {worst:.4f} (tolerance {tol:.4f})")
    return {"requests": len(reqs), "prompt_lens": [len(r.prompt) for r in reqs],
            "new_tokens": n_new, "serve_s": serve_s, "prefill_max_diff": diff,
            "logit_range": spread, "greedy_exact": exact, "greedy_max_gap": worst,
            "request0_tokens": reqs[0].output}


def phase_serve(seed: int, kind: str) -> dict:
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    depth = depth_for_one_chip(QWEN2, limit)
    cfg = dataclasses.replace(QWEN2, n_layers=depth)
    _log("serve", f"qwen2-7b: d_model {cfg.d_model}, {cfg.n_heads} heads over "
                  f"{cfg.n_kv_heads} kv, head dim {cfg.head_dim}, d_ff "
                  f"{cfg.d_ff}, vocab {cfg.vocab_size}, bf16; depth cut "
                  f"{QWEN2.n_layers} -> {depth} layers so the weights fit "
                  f"{WEIGHT_SHARE_OF_HBM:.0%} of {limit / 2**30:.2f} GiB HBM")
    t0 = time.perf_counter()
    params = jax.block_until_ready(random_weights(cfg, seed))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    _log("serve", f"weights {n_bytes / 2**30:.2f} GiB made on {kind} in "
                  f"{time.perf_counter() - t0:.1f} s")
    out = serve_and_check(cfg, params, seed, kind, "serve")
    return dict(out, depth=depth, weight_bytes=n_bytes)


def phase_serve_sharded(seed: int, kind: str) -> dict:
    mesh = make_host_mesh(model=4)
    set_mesh(mesh)
    cfg = QWEN2
    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        random_weights(cfg, seed, param_shardings(shapes, mesh, cfg)))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    _log("serve4", f"qwen2-7b, all {cfg.n_layers} layers: weights "
                   f"{n_bytes / 2**30:.2f} GiB made sharded over mesh "
                   f"{dict(mesh.shape)} in {time.perf_counter() - t0:.1f} s")
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d, n in held.items():
        stats = d.memory_stats()
        _log("serve4", f"device {d.id} ({d.device_kind}): weight shards "
                       f"{n / 2**30:.2f} GiB; memory_stats: "
                       f"{stats['bytes_in_use'] / 2**30:.2f} GiB in use of "
                       f"{stats['bytes_limit'] / 2**30:.2f} GiB")
    in_use = list(held.values())
    if max(in_use) > 0.5 * n_bytes:
        raise AssertionError(f"weights not spread over the mesh: {in_use} "
                             f"bytes per device for {n_bytes} bytes")
    out = serve_and_check(cfg, params, seed, kind, "serve4")
    return dict(out, depth=cfg.n_layers, weight_bytes=n_bytes,
                weight_bytes_per_device=in_use)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded qwen2-7b serve phase")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    device = phase_device(args.chips)
    kind = device["kind"]
    _log("device", f"compile cache at {cache_dir}")
    summary: dict = {"device": device, "seed": args.seed}
    phases = ({"serve4": phase_serve_sharded} if args.chips == 4 else
              {"kernels": phase_kernels, "search": phase_search,
               "serve": phase_serve})
    for name, phase in phases.items():
        t0 = time.perf_counter()
        summary[name] = phase(args.seed, kind)
        _log(name, f"passed in {time.perf_counter() - t0:.1f} s")
    out = ROOT / "results" / ("chip_smoke.json" if args.chips == 1
                              else f"chip_smoke_{args.chips}chips.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(summary, indent=1, default=str))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
