"""Serve a small model with batched requests: prefill + lockstep decode with
KV caches (ring buffers on SWA layers), mixed prompt lengths, greedy sampling.

  PYTHONPATH=src python examples/serve_batched.py --arch gemma2-27b
  PYTHONPATH=src python examples/serve_batched.py --arch mamba2-780m
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import BatchedServer, Request
from repro.models import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch).reduced()   # reduced = CPU-runnable weights
    print(f"serving {args.arch} (reduced config: {cfg.n_layers}L "
          f"d={cfg.d_model} vocab={cfg.vocab_size})")
    params = init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    (int(rng.integers(3, 12)),)).astype(np.int32),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]

    server = BatchedServer(cfg, params, batch_size=args.batch_size, max_len=64)
    t0 = time.time()
    done = server.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.output) for r in done)
    for r in done:
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.output}")
    dev = jax.devices()[0]
    print(f"\n{total_new} tokens in {dt:.1f}s ({total_new / dt:.1f} tok/s "
          f"batched on {dev.platform} {dev.device_kind}, compiles included)")


if __name__ == "__main__":
    main()
