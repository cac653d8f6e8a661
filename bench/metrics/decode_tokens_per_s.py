"""Tokens decoded in the window (sessions x steps completed), over the window."""


def read(run):
    if run.kind != "decode":
        return None
    return sum(c["batch"] for c in run.calls) / run.window_s
