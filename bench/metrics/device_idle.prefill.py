"""Share of the traced prefill window in which no operation ran on the device, in %."""


def read(run):
    if run.kind != "prefill" or run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
