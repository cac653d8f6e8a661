"""The unified telemetry plane (repro.core.obs): bounded event ring, metrics
registry, trace propagation end-to-end over the service wire (negotiated like
compact/shm — legacy workers see byte-identical frames), the JSONL run
journal + report CLI, and the plane's two hard guarantees — zero-cost when
disabled, lineage-inert when enabled (bit-identical lineages obs off vs on
across every eval backend)."""
import concurrent.futures as cf
import json
import socket
import threading

import pytest

from repro.core import IslandEvolution, Scorer, obs, seed_genome
from repro.core.evals import EvalCoordinator, EvalSpec, protocol
from repro.core.evals.elastic import ElasticProcessPool
from repro.core.evals.service import _worker_env
from repro.core.obs import report
from repro.core.perfmodel import BenchConfig

FAST_SUITE = [BenchConfig("c4k", 8, 16, 16, 4096, causal=True),
              BenchConfig("n4k", 8, 16, 16, 4096, causal=False)]


@pytest.fixture
def obs_on(tmp_path):
    """Enable telemetry for one test, journal into tmp, restore after."""
    prev = obs.enabled()
    obs.set_enabled(True)
    obs.BUS.ring.clear()
    yield tmp_path
    obs.close_journal()
    obs.set_enabled(prev)
    obs.BUS.ring.clear()


# -- the bounded event ring --------------------------------------------------------


def test_ring_bounds_and_counts_drops():
    r = obs.EventRing(cap=3)
    for i in range(5):
        r.append({"i": i})
    assert len(r) == 3
    assert r.dropped == 2
    assert [e["i"] for e in r] == [2, 3, 4]     # newest survive


def test_ring_quacks_like_the_list_it_replaced():
    r = obs.EventRing(cap=8)
    assert not r                                 # empty ring is falsy
    r.append({"event": "join"})
    r.append({"event": "leave"})
    assert r and len(r) == 2
    assert r[0]["event"] == "join" and r[-1]["event"] == "leave"
    assert [e["event"] for e in r[1:]] == ["leave"]          # slice view
    assert sorted(r, key=lambda e: e["event"])[0]["event"] == "join"
    assert list(r) == r.snapshot()
    with pytest.raises(ValueError):
        obs.EventRing(cap=0)


def test_coordinator_event_window_is_bounded(monkeypatch):
    monkeypatch.setenv("REPRO_OBS_EVENT_CAP", "1")
    coord = EvalCoordinator()
    socks = []
    try:
        for i in range(3):
            s = socket.create_connection(coord.address)
            socks.append(s)
            protocol.send_msg(s, {"type": protocol.HELLO, "name": f"w{i}",
                                  "slots": 1})
            assert protocol.recv_msg(s)["type"] == protocol.WELCOME
        assert coord.wait_for_workers(3, timeout=10)
        st = coord.stats()
        assert len(st["events"]) == 1            # window capped
        assert st["events_dropped"] >= 2         # shed joins are counted
        assert st["joined"] == 3                 # ...but totals are counters
    finally:
        for s in socks:
            s.close()
        coord.close()


def test_engine_commit_window_bounded_and_reported(monkeypatch):
    monkeypatch.setenv("REPRO_OBS_COMMIT_CAP", "2")
    eng = IslandEvolution(n_islands=2, suite=FAST_SUITE, seed=11,
                          migration_interval=2, check_correctness=False)
    try:
        rep = eng.run(max_steps=4)
    finally:
        eng.close()
    assert len(eng.commit_events) <= 2
    assert rep.commit_events_dropped == eng.commit_events.dropped
    if rep.commits > 2:
        assert rep.commit_events_dropped >= rep.commits - 2


# -- the metrics registry ----------------------------------------------------------


def test_registry_get_or_create_identity_and_kind_guard():
    reg = obs.MetricsRegistry()
    a = reg.counter("evals", island="i0")
    b = reg.counter("evals", island="i0")
    assert a is b                                # one instrument per key
    a.inc()
    a.inc(3)
    assert b.value == 4
    assert reg.counter("evals", island="i1").value == 0   # labels split
    with pytest.raises(TypeError):
        reg.gauge("evals", island="i0")          # same name, wrong kind
    g = reg.gauge("depth")
    g.set(7)
    snap = {(s["name"], tuple(sorted(s.get("labels", {}).items())))
            for s in reg.snapshot()}
    assert ("evals", (("island", "i0"),)) in snap
    reg.reset()
    assert reg.snapshot() == []


def test_legacy_stats_surfaces_read_the_registry():
    sc = Scorer(suite=FAST_SUITE, check_correctness=False)
    g = seed_genome()
    sc(g)
    sc(g)
    assert (sc.cache.misses, sc.cache.hits) == (1, 1)   # property view
    stats = sc.cache.stats()
    assert stats["hits"] == 1 and stats["misses"] == 1


# -- trace propagation -------------------------------------------------------------


def test_trace_binding_nests_and_restores():
    assert obs.current_trace() is None
    t1, t2 = obs.new_trace(), obs.new_trace()
    assert t1 != t2
    with obs.use_trace(t1):
        assert obs.current_trace() == t1
        with obs.use_trace(t2):
            assert obs.current_trace() == t2
        assert obs.current_trace() == t1
    assert obs.current_trace() is None


def test_console_sink_prints_narration_only(obs_on, capsys):
    obs.span("score", obs.new_trace(), dur_s=0.5)
    obs.narrate("[epoch 3] best=12.0 TFLOPS")
    out = capsys.readouterr().out
    assert "[epoch 3] best=12.0 TFLOPS" in out
    assert "score" not in out                    # spans stay off the console


def test_worker_env_propagates_obs_toggle():
    prev = obs.enabled()
    try:
        obs.set_enabled(True)
        assert _worker_env()["REPRO_OBS"] == "1"
        obs.set_enabled(False)
        assert _worker_env()["REPRO_OBS"] == "0"
    finally:
        obs.set_enabled(prev)


# -- the wire: capability-negotiated tracing ---------------------------------------


def _hello(sock, **caps):
    protocol.send_msg(sock, {"type": protocol.HELLO, "slots": 2,
                             "host": "elsewhere", **caps})
    assert protocol.recv_msg(sock)["type"] == protocol.WELCOME


def test_legacy_worker_never_sees_a_trace_field(obs_on):
    """A worker that does not advertise ``trace`` receives frames with no
    trace key even while the submitter traces — same negotiation contract
    as compact/shm, so pre-trace binaries are untouched."""
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    genomes = [seed_genome().with_(block_q=bq) for bq in (64, 256)]
    coord = EvalCoordinator()
    legacy = socket.create_connection(coord.address)
    compact = None
    try:
        _hello(legacy, name="old")               # no compact, no trace
        assert coord.wait_for_workers(1, timeout=10)
        coord.submit_many(spec, genomes, trace=obs.new_trace())
        for _ in genomes:
            msg = protocol.recv_msg(legacy)
            assert msg["type"] == protocol.TASK
            assert "trace" not in msg
        legacy.close()
        legacy = None

        compact = socket.create_connection(coord.address)
        _hello(compact, name="mid", compact=True)   # compact but no trace
        assert coord.wait_for_workers(1, timeout=10)
        coord.submit_many(spec, genomes, trace=obs.new_trace())
        msg = protocol.recv_msg(compact)
        assert msg["type"] == protocol.TASKS
        assert "trace" not in msg
    finally:
        for s in (legacy, compact):
            if s is not None:
                s.close()
        coord.close()


def test_traced_frames_carry_the_map_and_untraced_none(obs_on):
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    coord = EvalCoordinator()
    s = socket.create_connection(coord.address)
    try:
        _hello(s, name="new", compact=True, trace=True)
        assert coord.wait_for_workers(1, timeout=10)
        tr = obs.new_trace()
        coord.submit(spec, seed_genome().with_(block_q=64), trace=tr)
        msg = protocol.recv_msg(s)
        assert msg["type"] == protocol.TASKS
        (tid, _payload), = msg["tasks"]
        assert dict(msg["trace"]) == {tid: (tr, 0)}
        # an untraced submission to the same capable worker carries no map
        coord.submit(spec, seed_genome().with_(block_q=256), trace=None)
        msg2 = protocol.recv_msg(s)
        assert "trace" not in msg2
    finally:
        s.close()
        coord.close()


def test_spans_stitch_across_worker_death_and_requeue(obs_on):
    """The SIGKILL-shaped fault path: worker A takes a traced task and dies
    holding it; the task requeues (attempt 1) onto worker B, which returns
    spans.  The journal/ring must show BOTH dispatch attempts, the requeue,
    and B's worker-side spans — one stitched eval timeline."""
    spec = EvalSpec.resolve(FAST_SUITE, check_correctness=False)
    coord = EvalCoordinator(heartbeat_s=0.2)
    a = socket.create_connection(coord.address)
    b = None
    try:
        _hello(a, name="doomed", compact=True, trace=True)
        assert coord.wait_for_workers(1, timeout=10)
        tr = obs.new_trace()
        fut = coord.submit(spec, seed_genome().with_(block_q=128), trace=tr)
        msg = protocol.recv_msg(a)
        (tid, _payload), = msg["tasks"]
        assert dict(msg["trace"])[tid] == (tr, 0)
        a.close()                                # synchronous death, task held
        a = None

        b = socket.create_connection(coord.address)
        _hello(b, name="savior", compact=True, trace=True)
        msg = protocol.recv_msg(b)               # the requeued task
        (tid2, _payload), = msg["tasks"]
        assert dict(msg["trace"])[tid2] == (tr, 1)   # second attempt
        protocol.send_msg(b, {
            "type": protocol.RESULT, "id": tid2, "ok": True, "value": "sv",
            "spans": ({"span": "deserialize", "dur_s": 0.001},
                      {"span": "score", "dur_s": 0.25, "rung": "perfmodel"})})
        assert fut.result(10) == "sv"

        evs = [e for e in obs.BUS.ring.snapshot() if e.get("trace") == tr]
        dispatches = [e for e in evs if e.get("span") == "dispatch"]
        assert [(d["worker"], d["attempt"]) for d in dispatches] == \
            [("doomed", 0), ("savior", 1)]
        assert any(e.get("span") == "requeue" and e["attempt"] == 1
                   for e in evs)
        score = next(e for e in evs if e.get("span") == "score")
        assert (score["worker"], score["attempt"]) == ("savior", 1)
        assert score["rung"] == "perfmodel"
        st = coord.stats()
        assert st["tasks_requeued"] == 1 and st["tasks_completed"] == 1
    finally:
        for s in (a, b):
            if s is not None:
                s.close()
        coord.close()


# -- journal + report CLI ----------------------------------------------------------


def test_journal_roundtrip_and_report_cli(obs_on, capsys):
    path = obs.ensure_journal(run_id="t-report", root=str(obs_on))
    tr = obs.new_trace()
    obs.span("submit", tr, backend="thread", n=1)
    obs.span("score", tr, dur_s=0.25, rung="perfmodel")
    obs.publish("commit", trace=tr, island="island0", geomean=12.5)
    obs.narrate("[step 0] committed=True")
    obs.close_journal()

    events = report.load_journal(path)
    s = report.summarize(events)
    assert s["kinds"]["span"] == 2 and s["kinds"]["commit"] == 1
    assert s["kinds"]["narrate"] == 1
    assert s["traces"] == 1
    assert s["islands"]["island0"] == {"commits": 1, "best": 12.5}

    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert tr in out and "submit" in out and "commit" in out
    assert report.main([str(obs_on / "nope.jsonl")]) == 2


def test_journal_tolerates_a_torn_tail_line(obs_on):
    path = obs.ensure_journal(run_id="t-torn", root=str(obs_on))
    obs.publish("commit", island="i0", geomean=1.0)
    obs.close_journal()
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"event": "commit", "isl')      # killed writer mid-line
    events = report.load_journal(path)
    assert [e["event"] for e in events if e["event"] != "journal_open"] \
        == ["commit"]


def test_ensure_journal_noop_when_disabled(tmp_path):
    prev = obs.enabled()
    obs.set_enabled(False)
    try:
        assert obs.ensure_journal(run_id="x", root=str(tmp_path)) is None
        assert obs.journal_path() is None
        assert not (tmp_path / "x").exists()
    finally:
        obs.set_enabled(prev)


# -- resize/attach-failure events on the bus ---------------------------------------


def test_elastic_pool_resizes_publish_bus_events(obs_on):
    pool = ElasticProcessPool(
        slot_factory=lambda: cf.ThreadPoolExecutor(max_workers=1),
        min_workers=1, max_workers=3, grow_depth=0.5, hysteresis=1,
        shrink_idle_s=3600.0)
    gate = threading.Event()
    try:
        futs = [pool.submit(gate.wait, 10) for _ in range(6)]
        gate.set()
        for f in futs:
            f.result(10)
    finally:
        pool.shutdown(wait=True)
    grows = [e for e in obs.BUS.ring.snapshot() if e["event"] == "pool_grow"]
    assert grows, "growth must be mirrored onto the bus"
    assert all("depth" in e["why"] and e["workers"] >= 2 for e in grows)
    assert pool.stats()["grown"] == len(grows)   # same log, two surfaces


# -- the hard constraint: lineage-inert when enabled --------------------------------


IDENTITY_BACKENDS = ("inline", "thread", "process", "service")


def _fingerprints(**kw):
    eng = IslandEvolution(n_islands=2, suite=FAST_SUITE, seed=11,
                          migration_interval=2, check_correctness=False, **kw)
    try:
        eng.run(max_steps=4)
        return [[(c.genome.key(), round(c.geomean, 9), c.note)
                 for c in isl.lineage.commits] for isl in eng.islands]
    finally:
        eng.close()


@pytest.mark.parametrize("backend", IDENTITY_BACKENDS)
def test_lineages_bit_identical_obs_off_vs_on(backend, obs_on):
    kw = {"backend": backend}
    if backend == "service":
        kw["service_workers"] = 1
    obs.set_enabled(False)
    off = _fingerprints(**kw)
    obs.set_enabled(True)
    path = obs.ensure_journal(run_id=f"t-ident-{backend}", root=str(obs_on))
    on = _fingerprints(**kw)
    assert off == on
    # the enabled run actually observed: its journal holds the commits
    obs.close_journal()
    events = report.load_journal(path)
    assert any(e.get("event") == "commit" for e in events)
