"""The prefill attention kernel's share of its roofline, in %: the least
time its calls in the traced window could take (per call, the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, from the call's own
shapes) over the kernel's summed device time in the trace."""

# the instruction name the trace gives the kernel today ("flash_attention.3" and the like)
KERNEL = "flash_attention"


def read(run):
    if run.kind != "prefill" or run.trace is None:
        return None
    t = run.trace.op_time(lambda name: name.startswith(KERNEL))
    if t <= 0:
        return None
    cfg = run.cfg
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    least = sum(run.work.least_time(*run.work.attention_prefill(c["batch"], hq, hkv,
                                                                c["seq"], dh), run.peaks)
                for c in run.calls) * cfg["num_hidden_layers"]
    return 100.0 * least / t
