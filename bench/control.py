"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/control.py --workload <cell> --seeds 1-12 --control-seeds 1-3 \
        --seconds <run_seconds>

In one process, for each seed: the cell's set-up and a window of
``--seconds`` (0 for prefill: one whole cycle of lengths), then the
comparison a run makes (the program against the float32 reference) and, for
the control seeds, the same comparison with each of the reference's
``CONTROLS`` (int8 matmuls; fp8 attention) put in the program's place at the
same positions.  Prints one JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_compile_cache(cell.root)
    run.require_chip(cell.chips, cell.root)
    control = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    for seed in _seeds(args.seeds):
        st = run.setup(cell, seed)
        win = run.window(st, args.seconds)
        run.release_program(st)
        mark = time.perf_counter()
        line = {"workload": cell.name, "seed": seed, "calls": len(win.calls),
                "program": run.compare(st, win)}
        line["reference_s"] = time.perf_counter() - mark
        if seed in control:
            line["control"] = {c: run.compare(st, win, control=c) for c in st.ref.CONTROLS}
        print(json.dumps(line), flush=True)
        del st, win
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
