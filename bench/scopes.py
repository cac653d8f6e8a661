"""Device time of the model step by program scope, read from a profiler trace.

The program labels each layer boundary of its step with ``jax.named_scope``
(the names are listed in ``repro/models/transformer.py``).  XLA keeps the
label path in every op's metadata, and the profiler writes it as the
``tf_op`` stat of the op's event metadata on the device plane, for example
``jit(run)/layers/while/body/closed_call/attn/kernel/jit(flash_attention)/
flash_attention/pallas_call``.  ``jax.profiler.ProfileData`` does not expose
metadata stats, so this module reads the ``.xplane.pb`` itself with a small
reader of the protobuf wire format (``XSpace`` -> ``XPlane``): the device
planes' event and stat metadata and their ``XLA Ops`` line, and the host's
``request`` spans.  Everything else is skipped by its length.

Each op's self time (``xplane``'s rule for nested events), clipped to the
window of ``xplane.reduce`` (first ``request`` start to last ``request``
end), is charged to the path of program scopes on its ``tf_op``
(``layers/attn/kernel``); an op with none goes to ``unscoped``.  Seconds are
summed over devices and over the programs in the window.
"""
from __future__ import annotations

import functools
import os
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

from bench.xplane import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE, WINDOW_SPAN, _clip,
                          _self_times, find_xplane, op_name)

# the program's scope names (repro/models/transformer.py); other parts of an
# op path (jit(...), while, body, the op's own name) are not scopes
SCOPES = frozenset({"embed", "layers", "attn", "qkv", "kernel", "kv_cache", "out",
                    "mlp", "moe", "ssm", "head"})
UNSCOPED = "unscoped"
TF_OP = "tf_op"
TRACE_DIR = ".bench_trace"       # where bench/run.py writes a traced run's trace

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


@dataclass
class Scopes:
    seconds: dict = field(default_factory=dict)   # scope path -> self seconds in the window
    n_devices: int = 0
    ops: dict = field(default_factory=dict)       # op name -> tf_op, every device op seen

    def time(self, scope: str) -> float:
        """Seconds charged to paths whose innermost scope is ``scope``."""
        return sum(t for path, t in self.seconds.items() if innermost(path) == scope)

    @property
    def labelled(self) -> bool:
        """Whether any op in the window carries a program scope."""
        return any(path != UNSCOPED for path in self.seconds)


def scope_path(tf_op: str) -> str:
    """The program scopes on an op path, outermost first, joined by ``/``.
    XLA joins the paths of merged ops with ``;``, the first being the op's
    own; the profiler ends a path with ``:`` and the op's type."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    return "/".join(p for p in path.split("/") if p in SCOPES) or UNSCOPED


def innermost(path: str) -> str:
    return path.rsplit("/", 1)[-1]


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf, i):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i + 1
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one message: an int for a varint, a
    (start, end) range for a length-delimited field; fixed-width skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans):
    """Key and value range of each entry of a protobuf ``map<int64, msg>``."""
    for span in spans:
        key = value = None
        for f, v in _fields(buf, *span):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        if value is not None:
            yield key, value


def _names(buf, spans):
    """id -> name of ``XEventMetadata`` or ``XStatMetadata`` map entries."""
    out = {}
    for key, value in _map_entries(buf, spans):
        for f, v in _fields(buf, *value):
            if f == _META_NAME:
                out[key] = _text(buf, v)
                break
    return out


def _plane(buf, span, want):
    """A plane's name, the ranges of its lines and metadata maps; ``None``
    as soon as its name shows that ``want`` does not take it."""
    name, lines, event_meta, stat_meta = None, [], [], []
    for f, v in _fields(buf, *span):
        if f == _PLANE_NAME:
            name = _text(buf, v)
            if not want(name):
                return None
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_META:
            event_meta.append(v)
        elif f == _PLANE_STAT_META:
            stat_meta.append(v)
    return name, lines, event_meta, stat_meta


def _line_events(buf, span, want_line):
    """[(metadata id, start ns, end ns)] of a line that ``want_line`` takes
    by name, else ``None``."""
    name, ts_ns, events = None, 0, []
    for f, v in _fields(buf, *span):
        if f == _LINE_NAME:
            name = _text(buf, v)
            if not want_line(name):
                return None
        elif f == _LINE_TIMESTAMP_NS:
            ts_ns = v
        elif f == _LINE_EVENTS:
            mid = offset = duration = 0
            for g, w in _fields(buf, *v):
                if g == _EVENT_META_ID:
                    mid = w
                elif g == _EVENT_OFFSET_PS:
                    offset = w
                elif g == _EVENT_DURATION_PS:
                    duration = w
            events.append((mid, offset, duration))
    return [(mid, ts_ns + offset / 1e3, ts_ns + (offset + duration) / 1e3)
            for mid, offset, duration in events]


def _tf_ops(buf, event_meta, stat_meta):
    """metadata id -> (op name, tf_op or ``None``) of a device plane."""
    stat_names = _names(buf, stat_meta)
    tf_op_ids = {k for k, n in stat_names.items() if n == TF_OP}
    out = {}
    for key, value in _map_entries(buf, event_meta):
        name, tf_op = "", None
        for f, v in _fields(buf, *value):
            if f == _META_NAME:
                name = _text(buf, v)
            elif f == _EVENT_META_STATS:
                stat = dict(_fields(buf, *v))
                if stat.get(_STAT_META_ID) in tf_op_ids:
                    if _STAT_STR in stat:
                        tf_op = _text(buf, stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        tf_op = stat_names.get(stat[_STAT_REF])
        out[key] = (op_name(name), tf_op)
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def read(path: str) -> Scopes:
    """Self seconds by scope path of a ``.xplane.pb``; cached by the file's
    path, size and modification time."""
    st = os.stat(path)
    return _read(str(path), st.st_size, st.st_mtime_ns)


def for_checkout(root) -> Scopes:
    """The scopes of the trace a traced run of ``bench/run.py`` left in
    the checkout ``root``."""
    return read(find_xplane(str(Path(root) / TRACE_DIR)))


@functools.lru_cache(maxsize=4)
def _read(path: str, size: int, mtime_ns: int) -> Scopes:
    with open(path, "rb") as f:
        buf = f.read()

    def want(name):
        return name == HOST_PLANE or name.startswith(DEVICE_PREFIX)

    requests, devices = [], []
    for f, span in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        plane = _plane(buf, span, want)
        if plane is None:
            continue
        name, lines, event_meta, stat_meta = plane
        if name == HOST_PLANE:
            ids = {k for k, n in _names(buf, event_meta).items() if n == WINDOW_SPAN}
            for line in lines:
                requests += [(s, e) for mid, s, e in _line_events(buf, line, lambda n: True)
                             if mid in ids]
            continue
        evs = [ev for line in lines
               for ev in (_line_events(buf, line, lambda n: n == OPS_LINE) or ())]
        if evs:
            devices.append((_tf_ops(buf, event_meta, stat_meta), evs))
    if not requests:
        raise ValueError(f"no '{WINDOW_SPAN}' host span in the trace")
    if not devices:
        raise ValueError("no device operation in the trace")
    lo = min(s for s, _ in requests)
    hi = max(e for _, e in requests)
    seconds, ops = defaultdict(float), {}
    for meta, evs in devices:
        for mid, s, e, self_ns in _self_times(evs):
            name, tf_op = meta.get(mid, ("", None))
            ops[name] = tf_op
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs or e <= s:
                continue
            seconds[scope_path(tf_op or "")] += self_ns * (ce - cs) / (e - s) * 1e-9
    return Scopes(seconds=dict(seconds), n_devices=len(devices), ops=ops)
