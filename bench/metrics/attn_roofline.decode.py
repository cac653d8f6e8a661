"""The decode attention kernel's share of its roofline, in %: the least time
its calls in the traced window could take (K and V up to each call's valid
length, plus q and o, over HBM bandwidth, or FLOPs over the peak if larger)
over the kernel's summed device time in the trace."""

# the instruction name the trace gives the kernel today ("flash_decode.3" and the like)
KERNEL = "flash_decode"


def read(run):
    if run.kind != "decode" or run.trace is None:
        return None
    t = run.trace.op_time(lambda name: name.startswith(KERNEL))
    if t <= 0:
        return None
    cfg = run.cfg
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    least = sum(run.work.least_time(*run.work.attention_decode(c["batch"], hq, hkv,
                                                               c["valid"], dh), run.peaks)
                for c in run.calls) * cfg["num_hidden_layers"]
    return 100.0 * least / t
