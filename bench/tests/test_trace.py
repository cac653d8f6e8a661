"""The trace reduction on hand-made traces whose answers are known, and on
a trace recorded on a TPU v5e by the benchmark's own traced run."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import xplane

FIXTURE = Path(__file__).parent / "data"


def _plane(pid, name, line, events, names):
    evs = "\n".join(f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {s * 1000} "
                    f"duration_ps: {d * 1000} }}" for n, s, d in events)
    meta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
                     for i, n in enumerate(names))
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 0 {evs} }} {meta} }}")


def _profile(device_events, host_events, n_devices=1):
    dn = sorted({e[0] for e in device_events})
    hn = sorted({e[0] for e in host_events})
    planes = [_plane(10 + i, f"/device:TPU:{i}", "XLA Ops", device_events, dn)
              for i in range(n_devices)]
    planes.append(_plane(1, "/host:CPU", "python", host_events, hn))
    return ProfileData.from_text_proto("\n".join(planes))


# times in ns: two requests, [100, 400) and [500, 1000)
HOST = [("request", 100, 300), ("prepare", 100, 20), ("dispatch", 120, 30),
        ("sync", 150, 250), ("request", 500, 500), ("prepare", 500, 100),
        ("dispatch", 600, 50), ("sync", 650, 350)]
DEVICE = [("warmup", 0, 90),                  # before the window: left out
          ("while.1", 150, 200),              # holds the two below
          ("_fa_body_grid", 160, 80), ("fusion.3", 250, 50),
          ("fusion.4", 660, 100), ("_fa_body_grid", 740, 160),   # overlaps fusion.4
          ("fusion.5", 980, 60)]              # runs past the window's end


def test_busy_union_idle_and_self_times():
    t = xplane.reduce(_profile(DEVICE, HOST))
    assert t.window_s == pytest.approx(900e-9)
    # busy: [150, 350) + [660, 900) + [980, 1000) = 200 + 240 + 20
    assert t.busy_s == pytest.approx(460e-9)
    assert t.idle_share == pytest.approx(1 - 460 / 900)
    assert "warmup" not in t.ops
    assert t.ops["while.1"] == pytest.approx(70e-9)            # 200 - 80 - 50
    assert t.op_time(lambda n: "_fa_body" in n) == pytest.approx(240e-9)
    assert t.ops["fusion.5"] == pytest.approx(20e-9)           # clipped to the window


def test_gaps_are_attributed_to_host_spans():
    t = xplane.reduce(_profile(DEVICE, HOST))
    # gaps: [100,150) prepare 20 / dispatch 30 -> dispatch; [350,660) sync of the
    # first request 50 ns vs prepare 100 + dispatch 50 of the second -> prepare;
    # [900,980) sync
    got = sorted((round(s * 1e9), label) for label, s in t.gaps)
    assert got == [(50, "dispatch"), (80, "sync"), (310, "prepare")]
    assert t.breakdown()["idle_gaps"][0] == ["prepare", pytest.approx(310e-9)]
    assert t.breakdown()["device_ops"][0][0] == "_fa_body_grid"


def test_busy_is_averaged_over_devices_and_ops_summed():
    t = xplane.reduce(_profile(DEVICE, HOST, n_devices=2))
    assert t.n_devices == 2
    assert t.busy_s == pytest.approx(460e-9)
    assert t.op_time(lambda n: "_fa_body" in n) == pytest.approx(480e-9)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce(_profile([], HOST))


# A trace of the benchmark's own traced run of qwen2-7b.prefill-long on a
# TPU v5 lite (seed 401, --seconds 10): 12 requests, 4 cycles of 4k/8k/16k.
CHIP_TRACE = FIXTURE / "prefill-long.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    return ProfileData.from_file(str(CHIP_TRACE)), xplane.reduce(str(CHIP_TRACE))


def test_chip_trace_window_and_busy_union(chip):
    profile, t = chip
    reqs = [(e.start_ns, e.end_ns) for p in profile.planes if p.name == xplane.HOST_PLANE
            for ln in p.lines for e in ln.events if e.name == "request"]
    assert len(reqs) == 12
    lo, hi = min(s for s, _ in reqs), max(e for _, e in reqs)
    assert t.window_s == pytest.approx((hi - lo) * 1e-9)
    # busy union recomputed by sweeping sorted boundaries
    evs = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for p in profile.planes
                 if p.name.startswith(xplane.DEVICE_PREFIX) for ln in p.lines
                 if ln.name == xplane.OPS_LINE for e in ln.events
                 if e.end_ns > lo and e.start_ns < hi)
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in evs:
        if cur_e is None or s > cur_e:
            busy += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    assert t.busy_s == pytest.approx(busy * 1e-9)
    assert t.n_devices == 1
    assert 0.0 < t.idle_share < 0.01          # read 0.30% on the chip


def test_chip_trace_kernel_time_and_gaps(chip):
    profile, t = chip
    kernel = sum(e.duration_ns for p in profile.planes if p.name.startswith(xplane.DEVICE_PREFIX)
                 for ln in p.lines if ln.name == xplane.OPS_LINE for e in ln.events
                 if xplane.op_name(e.name).startswith("flash_attention")) * 1e-9
    got = t.op_time(lambda n: n.startswith("flash_attention"))
    assert got == pytest.approx(kernel, rel=1e-6)        # all inside the window
    assert got / sum(t.ops.values()) == pytest.approx(0.752, abs=0.01)
    assert {label for label, _ in t.gaps} <= {"prepare", "dispatch", "sync", "other"}
    assert sum(s for _, s in t.gaps) == pytest.approx(t.window_s - t.busy_s)
    assert t.breakdown()["device_ops"][0][0] == "flash_attention.3"
