"""Device time by program scope (``bench/scopes.py``): on hand-made traces
whose answers are known, and on traces recorded on a TPU v5e by the
benchmark's own traced runs."""
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes, xplane

FIXTURE = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]


# ---------------------------------------------------------------------------
# a hand-made trace, written in the protobuf wire format
# ---------------------------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(data)) + data
    return out


TF_OP_STAT, OTHER_STAT = 7, 3


def _plane(name, line, events, metas, tf_ops=None):
    """events: (metadata id, start ns, duration ns); metas: id -> name;
    tf_ops: id -> a path as a string, or as an int naming a stat metadata
    entry whose name is the path (how the profiler shares long strings)."""
    tf_ops = tf_ops or {}
    refs = {v for v in tf_ops.values() if isinstance(v, int)}
    evs = [(4, _msg((1, mid), (2, s * 1000), (3, d * 1000))) for mid, s, d in events]
    em = []
    for mid, n in metas.items():
        stats = [(5, _msg((1, OTHER_STAT), (5, "loop fusion")))]
        if mid in tf_ops:
            v = tf_ops[mid]
            stats.append((5, _msg((1, TF_OP_STAT), (7, v) if isinstance(v, int) else (5, v))))
        em.append((4, _msg((1, mid), (2, _msg((1, mid), (2, n), *stats)))))
    sm = [(5, _msg((1, k), (2, _msg((1, k), (2, n)))))
          for k, n in [(TF_OP_STAT, "tf_op"), (OTHER_STAT, "hlo_category")]]
    sm += [(5, _msg((1, r), (2, _msg((1, r), (2, PATHS[r]))))) for r in refs]
    line_msg = _msg((1, 1), (2, line), (3, 0), *evs)
    return _msg((1, 9), (2, name), (3, line_msg), *em, *sm)


PATHS = {101: "jit(run)/layers/while/body/closed_call/attn/kernel/jit(flash_attention)/"
              "flash_attention/pallas_call:"}
DEVICE_METAS = {1: "%warmup = f32[]", 2: "%while.2 = (f32[])", 3: "%fusion.1 = bf16[8]",
                4: "%flash_attention.3 = bf16[8]", 5: "%copy.29 = bf16[8]",
                6: "%fusion.9 = bf16[8]", 7: "%fusion.5 = f32[8]"}
DEVICE_TF_OPS = {1: "jit(run)/embed/gather:",
                 3: "jit(run)/layers/while/body/closed_call/attn/qkv/dot_general:",
                 4: 101,
                 6: "jit(run)/layers/while/body/squeeze:",
                 7: "jit(run)/head/dot_general;head/reshape:"}
# times in ns; the window is [100, 1000): two requests
DEVICE = [(1, 0, 90),                    # before the window: left out
          (2, 150, 200),                 # the loop, no path: holds the two below
          (3, 160, 80), (4, 250, 50),
          (5, 660, 100),                 # a compiler's copy, no path
          (6, 760, 100),
          (7, 980, 60)]                  # runs past the window's end
HOST = [(1, 100, 300), (2, 120, 30), (1, 500, 500)]


def _space(n_devices=1):
    planes = [_plane(f"/device:TPU:{i}", "XLA Ops", DEVICE, DEVICE_METAS, DEVICE_TF_OPS)
              for i in range(n_devices)]
    planes.append(_plane("/host:metadata", "skipped", [(1, 0, 5)], {1: "request"}))
    planes.append(_plane("/host:CPU", "python3", HOST, {1: "request", 2: "dispatch"}))
    return b"".join(_msg((1, p)) for p in planes)


@pytest.fixture
def handmade(tmp_path):
    def write(n_devices=1):
        path = tmp_path / f"t{n_devices}.xplane.pb"
        path.write_bytes(_space(n_devices))
        return str(path)
    return write


@pytest.mark.parametrize("tf_op,path", [
    ("jit(run)/layers/while/body/closed_call/attn/qkv/dot_general:", "layers/attn/qkv"),
    ("jit(run)/layers/while/body/closed_call/attn/qkv/broadcast_in_dim;attn/qkv/reshape",
     "layers/attn/qkv"),
    (PATHS[101], "layers/attn/kernel"),
    ("jit(run)/layers/while/body/closed_call/attn/kv_cache/dynamic_update_slice:",
     "layers/attn/kv_cache"),
    ("jit(run)/layers/while:", "layers"),
    ("jit(run)/kv_cache/vmap(jit(_pad))/pad:", "kv_cache"),
    ("jit(run)/while/body/closed_call/dot_general:", "unscoped"),
    ("", "unscoped"),
])
def test_an_op_path_gives_its_program_scopes(tf_op, path):
    assert scopes.scope_path(tf_op) == path


def test_each_op_is_charged_to_its_innermost_scope(handmade):
    s = scopes.read(handmade())
    assert s.n_devices == 1
    got = {k: round(v * 1e9, 6) for k, v in s.seconds.items()}
    # the loop's self time: 200 - 80 - 50; the copy: 100; the head clipped to 20
    assert got == {"unscoped": 170.0, "layers/attn/qkv": 80.0, "layers/attn/kernel": 50.0,
                   "layers": 100.0, "head": 20.0}
    assert s.time("kernel") == pytest.approx(50e-9)
    assert s.time("layers") == pytest.approx(100e-9)      # the scope alone, not its sublayers
    assert s.time("attn") == 0.0
    assert s.labelled
    assert s.ops["flash_attention.3"] == PATHS[101]       # a path shared by reference
    assert s.ops["copy.29"] is None


def test_devices_are_summed_and_counted(handmade):
    one, two = scopes.read(handmade(1)), scopes.read(handmade(2))
    assert two.n_devices == 2
    assert two.seconds == pytest.approx({k: 2 * v for k, v in one.seconds.items()})


def test_a_trace_without_requests_or_device_ops_is_refused(tmp_path):
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_msg((1, _plane("/host:CPU", "python3", HOST, {1: "other"}))))
    with pytest.raises(ValueError, match="no 'request' host span"):
        scopes.read(str(path))
    path.write_bytes(_msg((1, _plane("/host:CPU", "python3", HOST, {1: "request"}))))
    with pytest.raises(ValueError, match="no device operation"):
        scopes.read(str(path))


# ---------------------------------------------------------------------------
# traces recorded on the chip
# ---------------------------------------------------------------------------

# qwen2-7b.prefill-long from a program that set no scopes (seed 401, 10 s)
UNLABELLED = FIXTURE / "prefill-long.xplane.pb"


def _reference_pb2():
    """The profiler's own message classes, loaded from their file alone (no
    TensorFlow import), as an independent reader of the same bytes."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        pytest.skip("no xplane_pb2 to compare with")
    path = Path(spec.submodule_search_locations[0]) / "tsl/profiler/protobuf/xplane_pb2.py"
    if not path.exists():
        pytest.skip("no xplane_pb2 to compare with")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2_reference", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("trace", ["prefill-long.xplane.pb"])
def test_a_tf_op_is_recovered_for_every_op_that_has_one(trace):
    pb2 = _reference_pb2()
    space = pb2.XSpace()
    space.ParseFromString((FIXTURE / trace).read_bytes())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        ids = {e.metadata_id for ln in plane.lines if ln.name == xplane.OPS_LINE
               for e in ln.events}
        for i in ids:
            meta = plane.event_metadata[i]
            tf_op = None
            for st in meta.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    kind = st.WhichOneof("value")
                    tf_op = stat_names[st.ref_value] if kind == "ref_value" else st.str_value
            want[xplane.op_name(meta.name)] = tf_op
    got = scopes.read(str(FIXTURE / trace)).ops
    assert got == want
    assert sum(v is not None for v in want.values()) > 0.9 * len(want)


def test_device_self_time_adds_up_to_the_op_reduction():
    s = scopes.read(str(UNLABELLED))
    t = xplane.reduce(str(UNLABELLED))
    assert sum(s.seconds.values()) / s.n_devices == pytest.approx(sum(t.ops.values()),
                                                                 rel=1e-3)
    assert not s.labelled                     # this program set no scopes


def _checkout(tmp_path, trace):
    """A checkout holding the two metric files and, where a traced run
    leaves it, ``trace``."""
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    for name in ("mlp_roofline.prefill", "stack_moves.decode"):
        shutil.copy(ROOT / "bench" / "metrics" / f"{name}.py", tmp_path / "bench" / "metrics")
    dst = tmp_path / scopes.TRACE_DIR / "plugins" / "profile" / "run"
    dst.mkdir(parents=True)
    shutil.copy(trace, dst / "host.xplane.pb")
    return tmp_path


def _metric(root, name):
    spec = importlib.util.spec_from_file_location(name, root / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_new_metrics_read_nothing_where_the_program_sets_no_scopes(tmp_path):
    from bench import work
    root = _checkout(tmp_path, UNLABELLED)
    cfg = {"hidden_size": 3584, "intermediate_size": 18944, "mlp": "gated_silu",
           "num_hidden_layers": 8}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = xplane.reduce(str(UNLABELLED))
    for kind, name in (("prefill", "mlp_roofline.prefill"), ("decode", "stack_moves.decode")):
        run = SimpleNamespace(kind=kind, cfg=cfg, trace=trace, work=work, peaks=peaks,
                              calls=[{"batch": 1, "seq": 4096}])
        assert _metric(root, name)(run) is None
        assert _metric(root, name)(SimpleNamespace(**{**vars(run), "trace": None})) is None


# The scoped program's own traced runs (TPU v5 lite): qwen2-7b.prefill-long,
# seed 13101, --seconds 1 (one cycle: 3 requests), and qwen2-7b.decode-long,
# seed 13102, --seconds 2 (28 steps).  The numbers are those the runs printed.
SCOPED = {"prefill": (FIXTURE / "prefill-long-scoped.xplane.pb", 13101, 3,
                      "mlp_roofline.prefill", 87.49175525775361),
          "decode": (FIXTURE / "decode-long-scoped.xplane.pb", 13102, 28,
                     "stack_moves.decode", 43.85216336635621)}


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_busy_time_is_charged_to_the_programs_scopes(kind):
    path = str(SCOPED[kind][0])
    s, t = scopes.read(path), xplane.reduce(path)
    busy = sum(s.seconds.values())
    assert busy == pytest.approx(sum(t.ops.values()), rel=1e-3)
    assert busy == pytest.approx(t.busy_s, rel=1e-3)      # no op nests in another here
    named = busy - s.seconds.get(scopes.UNSCOPED, 0.0)
    no_path = {name: t.ops[name] for name, tf_op in s.ops.items()
               if tf_op is None and name in t.ops}
    # every op that carries an op path lies in a program scope, but for the
    # harness's own argmax after the step (microseconds)
    assert named == pytest.approx(busy - sum(no_path.values()), rel=1e-4)
    if kind == "prefill":
        assert named >= 0.97 * busy
        assert s.time("kernel") / busy == pytest.approx(0.7556, abs=0.005)
    else:
        # the decode step's unscoped 21%: the two copies of the whole stacked
        # K and V cache that the compiler adds after the layer loop, with no
        # op name to charge them by
        big = {n: v for n, v in no_path.items() if v > 0.01 * busy}
        assert sorted(big) == ["copy.29", "copy.30"]
        assert named / busy == pytest.approx(0.7855, abs=0.005)
        assert s.time("layers") / busy == pytest.approx(0.4264, abs=0.005)


def _calls(kind, seed, n, cfg):
    """The first ``n`` calls of the cell's traffic from ``seed``, as the
    harness hands them to a metric."""
    from bench import traffic
    mix = traffic.load(f"{kind}-long", ROOT / "bench")
    if kind == "decode":
        return [{"batch": mix["sessions"]}] * n
    reqs = traffic.prefill_requests(mix, seed, cfg["vocab_size"])
    return [{"batch": mix["batch"], "seq": s} for (_, s, _), _ in zip(reqs, range(n))]


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_the_new_metrics_read_what_the_chip_run_printed(tmp_path, kind):
    import json

    from bench import work
    path, seed, n, name, printed = SCOPED[kind]
    root = _checkout(tmp_path, path)
    cfg = json.loads((ROOT / "bench" / "configs" / "qwen2-7b.json").read_text())
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    run = SimpleNamespace(kind=kind, cfg=cfg, trace=xplane.reduce(str(path)), work=work,
                          peaks=peaks, calls=_calls(kind, seed, n, cfg))
    value = _metric(root, name)(run)
    assert value == pytest.approx(printed, rel=1e-9)
    lo, hi = (80.0, 92.0) if kind == "prefill" else (40.0, 46.0)     # predicted ranges
    assert lo <= value <= hi
