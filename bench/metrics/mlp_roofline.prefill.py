"""The MLP's share of its roofline in prefill, in %: the least time the
window's MLP work could take, over the device self time that the trace
charges to the program's ``mlp`` scope.  Per call and per layer the least
time is the larger of 2·B·S·n_mlp·D·d_ff FLOPs over the bf16 peak and the
weights, input and output moved once over HBM bandwidth; n_mlp is 3 for a
gated MLP, 2 otherwise.  Nothing where the program labels no scope."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]      # the checkout the run traced into


def least_time(cfg, batch, seq, work, peaks) -> float:
    """Least seconds of one call's MLP, all layers."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_mlp = 3 if cfg["mlp"].startswith("gated") else 2
    flops = 2.0 * batch * seq * n_mlp * d * f
    nbytes = work.BYTES * (n_mlp * d * f + 2 * batch * seq * d)
    return work.least_time(flops, nbytes, peaks) * cfg["num_hidden_layers"]


def read(run):
    if run.kind != "prefill" or run.trace is None:
        return None
    t = scopes.for_checkout(ROOT).time("mlp")
    if t <= 0:
        return None
    least = sum(least_time(run.cfg, c["batch"], c["seq"], run.work, run.peaks)
                for c in run.calls)
    return 100.0 * least / t
