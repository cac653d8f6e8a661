"""95th percentile of the window's decode steps, each timed from its
dispatch to its tokens on the host: the gap between tokens a streaming
user sees."""
import numpy as np


def read(run):
    if run.kind != "decode":
        return None
    return float(np.percentile([(c["end"] - c["start"]) * 1e3 for c in run.calls], 95))
