"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run, named by the operation's HLO text; ops are summed under
the instruction name that text starts with (``%flash_attention.3 = ...``
-> ``flash_attention.3``).  Events on one line may nest (a loop holds the operations
of its body), so each event is charged its self time: its duration less that
of the events directly inside it.  The harness marks its own host work with
``jax.profiler.TraceAnnotation`` spans on the same clock: ``request`` around
each timed call, and inside it ``prepare``, ``dispatch`` and ``sync``.  The
window is the first ``request`` start to the last ``request`` end.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "request"
HOST_SPANS = ("prepare", "dispatch", "sync")
TOP = 10


@dataclass
class Trace:
    window_s: float
    busy_s: float                    # mean over the devices that ran anything
    n_devices: int
    ops: dict = field(default_factory=dict)     # op name -> self seconds (summed over devices)
    gaps: list = field(default_factory=list)    # [(host span, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, match) -> float:
        """Summed self seconds of the ops whose name ``match`` accepts."""
        return sum(t for name, t in self.ops.items() if match(name))

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t] for n, t in top],
                "idle_gaps": [[n, t] for n, t in self.gaps[:TOP]]}


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {directory}")
    return found[0]


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _self_times(events):
    """[(name, start, end, self)] for events that may nest on one line."""
    events = sorted(events, key=lambda x: (x[1], -x[2]))
    out, stack = [], []          # stack of [name, start, end, child_total]
    for name, s, e in events:
        while stack and s >= stack[-1][2]:
            n, ps, pe, c = stack.pop()
            out.append((n, ps, pe, pe - ps - c))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        n, ps, pe, c = stack.pop()
        out.append((n, ps, pe, pe - ps - c))
    return out


def reduce(profile) -> Trace:
    """``profile``: a ``jax.profiler.ProfileData``, or a path to one."""
    if isinstance(profile, str):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(profile)
    requests, spans = [], []
    devices = {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        requests.append((ev.start_ns, ev.end_ns))
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            evs = [(op_name(ev.name), ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if evs:
                devices[plane.name] = evs
    if not requests:
        raise ValueError(f"no '{WINDOW_SPAN}' host span in the trace")
    if not devices:
        raise ValueError("no device operation in the trace")
    lo = min(s for s, _ in requests)
    hi = max(e for _, e in requests)
    ops = defaultdict(float)
    busy_total = 0.0
    gaps = []
    spans.sort(key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    for evs in devices.values():
        inside = []
        for name, s, e, self_ns in _self_times(evs):
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            ops[name] += self_ns * (ce - cs) / (e - s) * 1e-9 if e > s else 0.0
            inside.append((cs, ce))
        busy = _union(inside)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_attribute(gs, ge, spans, starts), (ge - gs) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    n = len(devices)
    return Trace(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n * 1e-9,
                 n_devices=n, ops=dict(ops), gaps=gaps)


def _attribute(gs, ge, spans, starts) -> str:
    """The host span that overlaps the gap most, or ``other``.  The spans
    follow one another, so only those from the gap's start on can overlap."""
    best, label = 0.0, "other"
    j = bisect.bisect_left(starts, ge) - 1
    while j >= 0 and spans[j][2] > gs:
        name, s, e = spans[j]
        ov = min(e, ge) - max(s, gs)
        if ov > best:
            best, label = ov, name
        j -= 1
    return label
