"""HBM bytes the decode steps of the traced window must move (every weight
once a step, the valid K and V, the new K and V), over the window times the
chip's HBM bandwidth, in %."""


def read(run):
    if run.kind != "decode" or run.trace is None:
        return None
    nbytes = sum(run.work.decode_bytes(run.cfg, c["batch"], c["valid"]) for c in run.calls)
    return 100.0 * nbytes / (run.trace.window_s * run.peaks["hbm_bytes_per_s"])
