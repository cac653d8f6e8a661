"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything about it
is found by name: the configuration in ``bench/configs/<config>.json``, the
traffic mix in ``bench/traffic/<traffic>.json``, the cell's check in
``bench/workloads/<cell>.json``, and each metric's reader in
``bench/metrics/<metric>.py``.  The run makes the weights and inputs from the
seed, compiles and warms the cell's shapes (set-up), drives the program for
``--seconds`` (the window), then compares what the window produced with the
plain reference.  With ``--trace 1`` the window runs under the profiler and
the per-layer metrics are read from its trace; otherwise the end-to-end
metrics are printed.  The last line of stdout is one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]     # the package ``bench``, the program

import numpy as np  # noqa: E402

from bench import traffic  # noqa: E402

TRACE_DIR = ".bench_trace"        # under the checkout; replaced by each traced run
REF_SESSIONS = 2                  # decode sessions the reference takes at a time


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` with its configuration, traffic, check and the
    metrics it reports, all read from files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = json.loads((root / "bench" / "configs" / f"{w['config']}.json").read_text())
    cfg.setdefault("name", w["config"])
    mix = traffic.load(w["traffic"], root / "bench")
    check = json.loads((root / "bench" / "workloads" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return SimpleNamespace(name=name, chips=w["chips"], cfg=cfg, mix=mix, check=check,
                           end_to_end=e2e, per_layer=per_layer, root=root)


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(cfg: dict):
    return importlib.import_module(f"bench.families.{cfg['family']}")


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def require_chip(chips: int, root: Path = ROOT) -> dict:
    """The device as JAX reports it, and its peaks; exits without a TPU, with
    fewer chips than asked, or with a device kind the peaks table lacks."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found {d.platform}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if d.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {d.device_kind!r} in bench/peaks.json")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "peaks": peaks[d.device_kind]}


def memory_peak_bytes() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


# ---------------------------------------------------------------------------
# set-up and the window
# ---------------------------------------------------------------------------


def setup(cell, seed: int) -> SimpleNamespace:
    """Weights from the seed, the program's entry points, every shape of
    the cell's traffic compiled and run once; for decode the cache filled."""
    import jax
    fam = family(cell.cfg)
    ref = reference(cell.cfg)
    arch = fam.arch_config(cell.cfg)
    mark = time.perf_counter()
    w = jax.block_until_ready(ref.init_weights(cell.cfg, seed))
    params = fam.program_params(w, arch)
    st = SimpleNamespace(cell=cell, seed=seed, fam=fam, ref=ref, arch=arch, w=w,
                         params=params, splits={"init_s": mark - T_START})
    st.splits["weights_s"] = time.perf_counter() - mark
    mark = time.perf_counter()
    mix = cell.mix
    if mix["kind"] == "prefill":
        st.fn = fam.prefill_fn(arch, mix["max_len"])
        for s in sorted(set(mix["prompt_lens"])):
            toks = np.zeros((mix["batch"], s), np.int32)
            jax.block_until_ready(st.fn(params, jax.device_put(toks)))
        st.splits["warm_s"] = time.perf_counter() - mark
    else:
        st.fn = fam.decode_fn(arch)
        kv = traffic.kv_prefix(mix, seed, arch.n_layers, arch.n_kv_heads, arch.head_dim,
                               std=ref.kv_std(cell.cfg))
        st.cache = fam.decode_cache(arch, jax.block_until_ready(kv), mix["max_len"],
                                    mix["start"])
        del kv
        st.splits["cache_fill_s"] = time.perf_counter() - mark
        mark = time.perf_counter()
        st.tok0 = traffic.decode_tokens(mix, seed, arch.vocab_size)
        st.tok = jax.device_put(st.tok0)
        st.pos, st.epoch, st.served = mix["start"], 0, []
        for _ in range(mix["warmup_steps"]):
            _decode_step(st)
        st.splits["warm_s"] = time.perf_counter() - mark
    return st


def _decode_step(st):
    """One timed decode step: dispatch to greedy tokens on the host."""
    import jax
    mix = st.cell.mix
    with jax.profiler.TraceAnnotation("request"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("prepare"):
            if st.pos == mix["max_len"]:          # cache full: sessions restart
                st.cache = st.fam.restart(st.cache, mix["start"])
                st.tok = jax.device_put(st.tok0)
                st.pos, st.epoch = mix["start"], st.epoch + 1
        valid = st.pos + 1
        with jax.profiler.TraceAnnotation("dispatch"):
            st.tok, top, st.cache = st.fn(st.params, st.cache, st.tok)
        with jax.profiler.TraceAnnotation("sync"):
            host = np.asarray(st.tok), np.asarray(top)
        t1 = time.perf_counter()
    st.served.append((st.epoch,) + host)
    st.pos += 1
    return {"start": t0, "end": t1, "batch": mix["sessions"], "valid": valid}


def window(st, seconds: float) -> SimpleNamespace:
    """Drive the program for ``seconds``.  Prefill closes at the end of the
    first whole cycle of lengths after ``seconds``; decode at the first step
    completed after it."""
    import jax
    mix = st.cell.mix
    calls, kept = [], []
    t_win = time.perf_counter()
    if mix["kind"] == "prefill":
        n_lens = len(mix["prompt_lens"])
        for i, (cycle, s, toks) in enumerate(
                traffic.prefill_requests(mix, st.seed, st.arch.vocab_size)):
            with jax.profiler.TraceAnnotation("request"):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("prepare"):
                    dev = jax.device_put(toks)
                with jax.profiler.TraceAnnotation("dispatch"):
                    logits, tok, cache = st.fn(st.params, dev)
                with jax.profiler.TraceAnnotation("sync"):
                    served = np.asarray(tok)
                t1 = time.perf_counter()
            del cache
            calls.append({"start": t0, "end": t1, "batch": mix["batch"], "seq": s})
            kept.append((cycle, toks, logits, served))
            if i % n_lens == n_lens - 1 and t1 - t_win >= seconds:
                break
    else:
        while True:
            calls.append(_decode_step(st))
            if calls[-1]["end"] - t_win >= seconds:
                break
    return SimpleNamespace(t_start=t_win, window_s=calls[-1]["end"] - t_win,
                           calls=calls, kept=kept)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def release_program(st) -> None:
    """Free what the program holds on the device, keeping the weights."""
    for name in ("cache", "tok", "fn", "params"):
        if hasattr(st, name):
            delattr(st, name)
    gc.collect()


def compare(st, win, control=None) -> dict:
    """The numbers that decide ``correct``: ``token_gap``, the widest gap by
    which a served greedy token's logit lies below the reference's best, and
    ``logit_err``, the widest gap between a logit of the program and the
    reference's: over the whole vocabulary for prefill, at the served token
    for decode (the step returns that logit beside the token).  For prefill
    the sample is one whole cycle of the window (every length, the longest
    included), drawn from the seed, and ``rows`` rows of each of its batches,
    more than half of them so that no half of a batch goes unseen; for decode
    every session, every token of its first epoch, teacher-forced, in blocks
    of ``REF_SESSIONS``.  With ``control`` (one of the reference's
    ``CONTROLS``) the reference at that lower precision stands in for the
    program, read at the same positions."""
    import jax.numpy as jnp
    cfg, ref, check = st.cell.cfg, st.ref, st.cell.check
    rng = np.random.default_rng([st.seed, 2])
    gap = err = 0.0
    if st.cell.mix["kind"] == "prefill":
        cycles = sorted({c for c, *_ in win.kept})
        pick = cycles[int(rng.integers(len(cycles)))]
        rows = np.sort(rng.choice(st.cell.mix["batch"], check["rows"], replace=False))
        for cycle, toks, logits, served in win.kept:
            if cycle != pick:
                continue
            r = np.asarray(ref.logits(st.w, cfg, toks[rows]))[:, -1]
            if control:
                got = np.asarray(ref.logits(st.w, cfg, toks[rows], control=control))[:, -1]
                tok = got.argmax(-1)
            else:
                got = np.asarray(jnp.asarray(logits)[rows], np.float32)
                tok = served[rows]
            err = max(err, float(np.max(np.abs(got - r))))
            gap = max(gap, float(np.max(r.max(-1) - r[np.arange(len(rows)), tok])))
        return {"token_gap": gap, "logit_err": err}
    mix = st.cell.mix
    first = [(tok, top) for e, tok, top in st.served if e == 0]
    outs = np.stack([t for t, _ in first]).T                          # (sessions, steps)
    tops = np.stack([v for _, v in first]).T
    for lo in range(0, mix["sessions"], REF_SESSIONS):
        sess = np.arange(lo, min(lo + REF_SESSIONS, mix["sessions"]))
        out, got = outs[sess], tops[sess]
        inputs = np.concatenate([st.tok0[sess][:, None], out[:, :-1]], 1)
        prefix = traffic.kv_prefix(mix, st.seed, st.arch.n_layers, st.arch.n_kv_heads,
                                   st.arch.head_dim, std=ref.kv_std(cfg), sessions=sess,
                                   length=mix["start"])
        n = inputs.shape[1]
        r = np.asarray(ref.logits(st.w, cfg, inputs, prefix=prefix, n_last=n))
        if control:
            q = np.asarray(ref.logits(st.w, cfg, inputs, prefix=prefix, n_last=n,
                                      control=control))
            out = q.argmax(-1)
            got = np.take_along_axis(q, out[..., None], -1)[..., 0]
        picked = np.take_along_axis(r, out[..., None], -1)[..., 0]
        gap = max(gap, float(np.max(r.max(-1) - picked)))
        err = max(err, float(np.max(np.abs(got - picked))))
    return {"token_gap": gap, "logit_err": err}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the cell's file gives a limit, beside its limit; correct
    where none is above its limit."""
    checks = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


# ---------------------------------------------------------------------------
# metrics and the result line
# ---------------------------------------------------------------------------


def read_metrics(cell, win, device, tr) -> dict:
    from bench import work
    run = SimpleNamespace(kind=cell.mix["kind"], cfg=cell.cfg, mix=cell.mix,
                          calls=win.calls, window_s=win.window_s,
                          setup_s=win.t_start - T_START, trace=tr,
                          peaks=device["peaks"], work=work)
    metrics = {}
    for m in (cell.per_layer if tr is not None else cell.end_to_end):
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache in the checkout, at a path that
    never moves, handed to the program's own switch for it."""
    from repro.launch.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    enable_compile_cache()


def run_cell(cell, seed: int, seconds: float, traced: bool) -> dict:
    import jax
    use_compile_cache(cell.root)
    device = require_chip(cell.chips, cell.root)
    st = setup(cell, seed)
    gc.collect()
    gc.freeze()       # what set-up made is never scanned again by the collector
    trace_dir = cell.root / TRACE_DIR
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        win = window(st, seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    peak = memory_peak_bytes()
    tr = None
    if traced:
        from bench import xplane
        tr = xplane.reduce(xplane.find_xplane(str(trace_dir)))
    metrics = read_metrics(cell, win, device, tr)
    release_program(st)
    mark = time.perf_counter()
    numbers = compare(st, win)
    reference_s = time.perf_counter() - mark
    correct, checks = judge(numbers, cell.check["limits"])
    n_done = sum(c["batch"] for c in win.calls)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": n_done, "failed": 0,
              "metrics": metrics, "device": dev}
    if tr is not None:
        dev.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    result["setup"] = dict(st.splits, setup_s=win.t_start - T_START)
    durations = [c["end"] - c["start"] for c in win.calls]
    slowest = sorted(range(len(durations)), key=durations.__getitem__)[-3:][::-1]
    result["window"] = {"calls": len(durations),
                        "slowest_calls": [[i, durations[i]] for i in slowest],
                        "reference_s": reference_s}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
