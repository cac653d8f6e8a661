"""Device time a decode step spends on the layer scan's own moves, in ms:
the self time that the trace charges to the program's ``layers`` scope and
to no sublayer (slicing weights and caches out of the stack, stacking the
new caches), plus ops with no program scope (copies the compiler inserts),
over the decode steps of the traced window, per device.  Nothing where the
program labels no scope."""
from pathlib import Path

from bench import scopes

ROOT = Path(__file__).resolve().parents[2]      # the checkout the run traced into


def read(run):
    if run.kind != "decode" or run.trace is None:
        return None
    s = scopes.for_checkout(ROOT)
    if not s.labelled:
        return None
    moves = s.seconds.get("layers", 0.0) + s.seconds.get(scopes.UNSCOPED, 0.0)
    return 1e3 * moves / s.n_devices / len(run.calls)
