"""Model FLOPs of the prefills in the traced window (matmuls, attention's
valid pairs, the head on each sequence's last position; no recomputation),
over the window times the chip's bf16 peak, in %."""


def read(run):
    if run.kind != "prefill" or run.trace is None:
        return None
    flops = sum(run.work.prefill_flops(run.cfg, c["batch"], c["seq"]) for c in run.calls)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
