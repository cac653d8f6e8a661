"""Prompt tokens of every request completed in the window, over the window."""


def read(run):
    if run.kind != "prefill":
        return None
    return sum(c["batch"] * c["seq"] for c in run.calls) / run.window_s
