"""Model FLOPs of the decode steps in the traced window (matmuls, attention
over the valid keys, the head), over the window times the bf16 peak, in %."""


def read(run):
    if run.kind != "decode" or run.trace is None:
        return None
    flops = sum(run.work.decode_flops(run.cfg, c["batch"], c["valid"]) for c in run.calls)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_per_s"])
