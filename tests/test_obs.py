"""Dependability telemetry layer (docs/observability.md): the event bus,
the metrics registry (numpy as the percentile oracle), failure timelines
with MTTR/MTBF/availability, live Young/Daly adaptation, and the
record-and-replay loop (recorded JSONL -> Scenario -> ControlPlaneSim)."""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.obs import (DEFAULT_CAPACITY, Event, EventBus, MetricsRegistry,
                       Observability, SpanLog, Timeline, load_jsonl, span,
                       to_chrome_trace, to_scenario)
from repro.obs.metrics import NO_SPAN

# ---------------------------------------------------------------------------
# event bus
# ---------------------------------------------------------------------------


def test_bus_emit_stamps_and_filters():
    bus = EventBus()
    e1 = bus.emit("heartbeat", "failure", host=3)
    e2 = bus.emit("checkpoint", "save", step=10)
    assert e1.seq == 0 and e2.seq == 1
    assert e2.t_mono >= e1.t_mono and e1.t_wall > 0
    assert [e.kind for e in bus.events()] == ["failure", "save"]
    assert [e.data["host"] for e in bus.events(subsystem="heartbeat")] == [3]
    assert bus.events(kind="save")[0].data == {"step": 10}
    assert bus.events(subsystem="serve") == []
    assert len(bus) == 2 and bus.total_emitted == 2


def test_bus_ring_is_bounded_and_counts_drops():
    bus = EventBus(capacity=5)
    for i in range(12):
        bus.emit("s", "k", i=i)
    assert len(bus) == 5
    assert bus.dropped == 7
    assert bus.total_emitted == 12
    assert [e.data["i"] for e in bus.events()] == [7, 8, 9, 10, 11]
    assert EventBus().capacity == DEFAULT_CAPACITY
    with pytest.raises(ValueError):
        EventBus(capacity=0)


def test_bus_emit_rejects_reserved_payload_keys():
    """A payload key named like an Event field would silently shadow it
    in the flattened JSONL record (and TypeError on the kwarg path) —
    the bus refuses it up front."""
    bus = EventBus()
    # kind/subsystem hit emit's own parameters: loud TypeError from Python
    with pytest.raises(TypeError):
        bus.emit("checkpoint", "save", kind="full")
    # seq/t_mono/t_wall would pass through silently — the guard refuses
    with pytest.raises(ValueError, match="seq"):
        bus.emit("s", "k", seq=7, t_mono=0.0)
    assert len(bus) == 0 and bus.total_emitted == 0
    bus.emit("checkpoint", "save", save_kind="full")      # the renamed form
    assert bus.events()[0].data == {"save_kind": "full"}


def test_run_with_recovery_emits_interrupted_and_resume(tmp_path):
    """Fail-stop through the facade with telemetry attached: the recovery
    loop must put train/interrupted and train/resume on the bus (the
    interrupted emit once collided with the bus's own kind kwarg)."""
    import jax.numpy as jnp
    from repro.core.api import Dependability, DependabilityConfig
    from repro.core.coordinator import run_with_recovery
    from repro.core.failures import FaultInjector

    dep = Dependability(DependabilityConfig(
        checkpoint_dir=str(tmp_path / "ckpt"),
        policy_mode="every_n", every_n=2, signal_detection=False))
    obs = Observability()
    dep.attach_obs(obs)
    dep.start()
    state = {"step": jnp.array(0), "w": jnp.ones((4,))}
    dep.register_global_state(state)

    class Data:
        def next_batch(self):
            return jnp.ones((4,))

    def train_step(state, batch):
        w = state["w"] + 0.01
        return ({"step": state["step"] + 1, "w": w},
                {"loss": float(jnp.sum(w))})

    inj = FaultInjector(obs=obs)
    inj.schedule_failstop(4)
    state, rep = run_with_recovery(dep, train_step, state, Data(), 8,
                                   fault_injector=inj)
    assert rep["status"] == "done" and rep["restarts"] == 1
    kinds = {(e.subsystem, e.kind) for e in obs.events()}
    assert ("train", "interrupted") in kinds
    assert ("train", "resume") in kinds
    ints = obs.events(subsystem="train", kind="interrupted")
    assert ints[0].data["failure_kind"] == "fail-stop"
    assert obs.registry.histogram("train.rollback_depth").count == 1
    dep.stop()


def test_bus_concurrent_emitters_lose_nothing():
    """N threads hammer one bus while a subscriber (running on the
    emitting threads) collects: every event is delivered exactly once and
    sequence numbers are unique."""
    bus = EventBus(capacity=100_000)
    got, got_lock = [], threading.Lock()

    def on_event(ev):
        with got_lock:
            got.append(ev)

    bus.subscribe(on_event)
    threads_n, per_thread = 8, 500

    def worker(tid):
        for i in range(per_thread):
            bus.emit("t", "tick", tid=tid, i=i)

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads_n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = threads_n * per_thread
    assert bus.total_emitted == total and len(bus) == total
    assert len(got) == total
    seqs = [e.seq for e in bus.events()]
    assert sorted(seqs) == list(range(total))      # unique, gap-free
    # every (tid, i) pair delivered to the subscriber exactly once
    pairs = {(e.data["tid"], e.data["i"]) for e in got}
    assert len(pairs) == total


def test_bus_subscriber_may_inspect_bus_and_unsubscribe():
    bus = EventBus()
    seen = []

    def hook(ev):
        # callbacks run outside the lock: reading back must not deadlock
        seen.append((ev.kind, len(bus.events())))

    bus.subscribe(hook)
    bus.emit("s", "a")
    bus.unsubscribe(hook)
    bus.emit("s", "b")
    assert seen == [("a", 1)]


def test_bus_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "tele" / "events.jsonl")
    bus = EventBus()
    bus.attach_jsonl(path)                     # creates the parent dir
    bus.emit("heartbeat", "failure", host=2, detection_latency_s=0.21)
    bus.emit("chaos", "kill_hosts", at=6.0, until=None, hosts=[2, 3])
    bus.close()
    back = load_jsonl(path)
    assert [e.to_dict() for e in back] == [e.to_dict()
                                           for e in bus.events()]
    assert back[1].data["hosts"] == [2, 3] and back[1].data["until"] is None
    # re-attaching appends (the log survives a restart)
    bus.attach_jsonl(path)
    bus.emit("s", "more")
    bus.close()
    assert len(load_jsonl(path)) == 3


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=2.0, sigma=1.5, size=1500).tolist()
    reg = MetricsRegistry()
    h = reg.histogram("serve.latency_ms")
    for x in xs:
        h.observe(x)
    for q in (0.0, 25.0, 50.0, 90.0, 99.0, 100.0):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q),
                                                rel=1e-12)
    assert h.p50 == pytest.approx(np.percentile(xs, 50))
    assert h.count == 1500 and h.sum == pytest.approx(sum(xs))
    with pytest.raises(ValueError):
        h.percentile(101.0)


def test_histogram_window_bounds_percentiles_but_not_count():
    reg = MetricsRegistry()
    h = reg.histogram("x", window=64)
    xs = list(range(1000))
    for x in xs:
        h.observe(float(x))
    # percentiles over the newest 64 samples only; count/sum/min/max over
    # the full stream
    assert h.percentile(50) == pytest.approx(np.percentile(xs[-64:], 50))
    snap = h.snapshot()
    assert snap["count"] == 1000 and snap["min"] == 0.0
    assert snap["max"] == 999.0
    assert snap["mean"] == pytest.approx(np.mean(xs))


def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("sdc.detected", tier="abft")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("serve.queue_depth")
    g.set(7)
    g.inc()
    g.dec(2)
    assert g.value == 6


def test_registry_identity_labels_and_type_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.counter("a", host=1) is not reg.counter("a", host=2)
    assert reg.histogram("h") is reg.histogram("h")
    with pytest.raises(TypeError):
        reg.gauge("a")                        # "a" is already a Counter
    assert len(reg.instruments()) == 4


def test_span_times_into_histogram():
    reg = MetricsRegistry()
    with reg.span("checkpoint.restore_ms") as sp:
        time.sleep(0.01)
    assert sp.seconds >= 0.01
    h = reg.histogram("checkpoint.restore_ms")
    assert h.count == 1 and h.p50 == pytest.approx(sp.seconds * 1e3)


def test_span_records_interval_thread_and_histogram():
    obs = Observability()
    t_before = time.perf_counter()
    with obs.span("data.batch") as sp:
        time.sleep(0.005)

    def write():
        with obs.span("ckpt.write"):
            pass
    worker = threading.Thread(target=write)
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive()
    (n0, a0, b0, th0), (n1, a1, b1, th1) = obs.registry.spans.records()
    assert (n0, n1) == ("data.batch", "ckpt.write")
    assert t_before <= a0 < b0 <= a1 <= b1 <= time.perf_counter()
    assert b0 - a0 == pytest.approx(sp.seconds) and sp.seconds >= 0.005
    assert th0 == threading.get_ident() and th1 == worker.ident != th0
    h = obs.registry.histogram("data.batch")
    assert h.count == 1 and h.p50 == pytest.approx(sp.seconds * 1e3)
    assert obs.registry.spans.dropped == 0


def test_span_log_drops_the_oldest_past_its_size():
    reg = MetricsRegistry()
    reg.spans = SpanLog(capacity=3)
    for i in range(5):
        with reg.span(f"train.s{i}"):
            pass
    assert [r[0] for r in reg.spans.records()] == ["train.s2", "train.s3",
                                                   "train.s4"]
    assert reg.spans.dropped == 2
    with pytest.raises(ValueError):
        SpanLog(capacity=0)


def test_span_without_observability_is_the_shared_noop():
    a, b = span(None, "data.batch"), span(None, "train.sync")
    assert a is b is NO_SPAN
    with a:
        pass
    obs = Observability()
    with span(obs, "train.sync"):
        pass
    assert [r[0] for r in obs.registry.spans.records()] == ["train.sync"]


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serve.tokens").inc(42)
    reg.gauge("elastic.dp_width").set(4)
    h = reg.histogram("train.step_ms", host=0)
    h.observe(10.0)
    h.observe(20.0)
    text = reg.to_prometheus()
    assert "# TYPE serve_tokens counter" in text
    assert "serve_tokens 42" in text
    assert "# TYPE elastic_dp_width gauge" in text
    assert "elastic_dp_width 4" in text
    assert "# TYPE train_step_ms summary" in text
    assert 'train_step_ms{host="0",quantile="0.5"} 15' in text
    assert 'train_step_ms_count{host="0"} 2' in text
    assert 'train_step_ms_sum{host="0"} 30' in text
    assert "train.step_ms" not in text        # dots sanitized in names


def test_registry_snapshot_and_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert snap["c"] == 2 and snap["h"]["count"] == 1
    path = str(tmp_path / "metrics.json")
    reg.to_json(path)
    with open(path) as f:
        assert json.load(f) == json.loads(reg.to_json())


# ---------------------------------------------------------------------------
# failure timelines
# ---------------------------------------------------------------------------


def _ev(t, subsystem, kind, **data):
    return Event(seq=int(t * 1000), t_mono=t, t_wall=1e9 + t,
                 subsystem=subsystem, kind=kind, data=data)


def test_timeline_assembles_incidents_and_merges_detections():
    events = [
        _ev(0.0, "train", "step", step=0),
        _ev(1.0, "heartbeat", "failure", host=2),          # opens
        _ev(1.1, "sdc", "corruption", step=6),             # merges
        _ev(1.2, "elastic", "shrink", hosts=[2]),          # phase
        _ev(1.5, "checkpoint", "restore", step=4),         # phase
        _ev(2.0, "elastic", "resume", step=4),             # closes
        _ev(5.0, "serve", "replica_failed", replica=1),    # second incident
        _ev(5.5, "serve", "standby_activated", replica=4),
        _ev(6.0, "serve", "retry_first_token", rid=9),
        _ev(10.0, "train", "step", step=20),
    ]
    tl = Timeline.from_events(events)
    assert len(tl.incidents) == 2 and len(tl.closed) == 2
    first, second = tl.incidents
    assert first.cause == "heartbeat.failure"
    assert len(first.detections) == 2                      # merged, not split
    assert first.duration == pytest.approx(1.0)
    assert [k for _, k in first.phase_offsets_ms()] == [
        "sdc.corruption", "elastic.shrink", "checkpoint.restore",
        "resume:elastic.resume"]
    assert second.duration == pytest.approx(1.0)
    assert tl.mttr() == pytest.approx(1.0)
    assert tl.mtbf() == pytest.approx(4.0)                 # starts 1.0, 5.0
    assert tl.downtime() == pytest.approx(2.0)
    assert tl.availability() == pytest.approx(1.0 - 2.0 / 10.0)
    s = tl.summary()
    assert s["incidents"] == 2 and s["closed"] == 2
    assert s["causes"] == ["heartbeat.failure", "serve.replica_failed"]


def test_timeline_open_incident_counts_as_down_until_log_end():
    events = [
        _ev(0.0, "train", "step", step=0),
        _ev(4.0, "heartbeat", "failure", host=1),
        _ev(10.0, "train", "step", step=9),                # never resumed
    ]
    tl = Timeline.from_events(events)
    assert len(tl.closed) == 0 and tl.mttr() is None
    assert tl.mtbf() is None                               # one incident
    assert tl.downtime() == pytest.approx(6.0)
    assert tl.availability() == pytest.approx(0.4)
    inc = tl.incidents[0]
    assert inc.duration is None and inc.to_dict()["duration_s"] is None


def test_timeline_resume_without_incident_is_ignored():
    tl = Timeline.from_events([_ev(1.0, "train", "resume", step=3),
                               _ev(2.0, "train", "step", step=4)])
    assert tl.incidents == [] and tl.availability() == 1.0
    assert Timeline.from_events([]).availability() == 1.0


# ---------------------------------------------------------------------------
# exporters: chrome trace + record-and-replay
# ---------------------------------------------------------------------------


def test_chrome_trace_has_tracks_and_incident_bars():
    events = [
        _ev(1.0, "heartbeat", "failure", host=2),
        _ev(1.4, "checkpoint", "restore", step=4),
        _ev(2.0, "elastic", "resume", step=4),
    ]
    trace = to_chrome_trace(events)
    names = [t.get("name") for t in trace["traceEvents"]]
    assert "heartbeat.failure" in names and "elastic.resume" in names
    bars = [t for t in trace["traceEvents"] if t["ph"] == "X"]
    assert len(bars) == 1
    assert bars[0]["name"] == "incident:heartbeat.failure"
    assert bars[0]["dur"] == pytest.approx(1.0e6)          # us
    assert trace["otherData"]["summary"]["incidents"] == 1


def test_chrome_trace_renders_spans_per_thread():
    events = [_ev(1.0, "train", "step", step=1)]
    spans = [("data.batch", 1.5, 1.75, 11), ("ckpt.write", 0.5, 2.5, 22)]
    trace = to_chrome_trace(events, spans=spans)
    bars = {t["name"]: t for t in trace["traceEvents"]
            if t["ph"] == "X"}
    assert set(bars) == {"data.batch", "ckpt.write"}
    # microseconds from the earliest event or span (the write's start)
    assert bars["ckpt.write"]["ts"] == 0.0
    assert bars["ckpt.write"]["dur"] == pytest.approx(2.0e6)
    assert bars["data.batch"]["ts"] == pytest.approx(1.0e6)
    assert bars["data.batch"]["dur"] == pytest.approx(0.25e6)
    step = [t for t in trace["traceEvents"] if t["name"] == "train.step"]
    assert step[0]["ts"] == pytest.approx(0.5e6)
    tracks = {t["tid"]: t["args"]["name"] for t in trace["traceEvents"]
              if t["ph"] == "M"}
    span_tids = {bars["data.batch"]["tid"], bars["ckpt.write"]["tid"]}
    assert len(span_tids) == 2
    assert sorted(tracks[t] for t in span_tids) == ["spans: thread 11",
                                                   "spans: thread 22"]
    assert step[0]["tid"] not in span_tids


def test_dump_writes_the_span_log_into_the_trace(tmp_path):
    obs = Observability()
    obs.emit("train", "step", step=1)
    with obs.span("train.sync"):
        pass
    paths = obs.dump(str(tmp_path))
    with open(paths["trace"]) as f:
        trace = json.load(f)
    assert [t["name"] for t in trace["traceEvents"]
            if t["ph"] == "X"] == ["train.sync"]
    obs.close()


def test_to_scenario_declarative_round_trip_is_lossless():
    """The chaos driver records its compiled scenario on the bus; the
    converter reconstructs it bit-identically — name, clock, seed, and
    every event including window kinds."""
    from repro.chaos import Scenario, TrainScenarioDriver
    sc = (Scenario("compound", clock="step", seed=42)
          .kill_hosts([2, 3], at=6)
          .sdc_storm(rate=0.3, window=(4, 10))
          .traffic_spike(mult=4, window=(3, 12))
          .rejoin(2, at=16)
          .rejoin(3, at=16))

    class _E:
        send_filter = None

        def pause(self):
            pass

        def resume(self):
            pass

    obs = Observability()
    TrainScenarioDriver(sc, emitters={h: _E() for h in range(4)},
                        leaf_names=["params.w"], settle_seconds=0, obs=obs)
    back = obs.to_scenario()
    assert back.to_dict() == sc.to_dict()
    assert back.seed == 42 and back.clock == "step"
    assert back.name == "compound"
    # the name override still applies
    assert obs.to_scenario(name="renamed").name == "renamed"


def test_to_scenario_declarative_survives_jsonl(tmp_path):
    """Record -> JSONL on disk -> load -> Scenario: the full durable loop."""
    from repro.chaos import Scenario, TrainScenarioDriver
    sc = Scenario("s", seed=9).kill_hosts([1], at=3).rejoin(1, at=8)

    class _E:
        send_filter = None

        def pause(self):
            pass

        def resume(self):
            pass

    path = str(tmp_path / "events.jsonl")
    obs = Observability(jsonl_path=path)
    TrainScenarioDriver(sc, emitters={0: _E(), 1: _E()},
                        settle_seconds=0, obs=obs)
    obs.close()
    back = to_scenario(load_jsonl(path))
    assert back.to_dict() == sc.to_dict()


def test_to_scenario_derived_from_detections_replays_through_sim():
    """No chaos events on the bus (a "production" log): the converter
    derives a time-clock scenario from raw heartbeat detections, and the
    result drives the control-plane simulator."""
    from repro.chaos import ControlPlaneSim
    events = [
        _ev(0.0, "train", "step", step=0),
        _ev(0.5, "heartbeat", "failure", host=1, detection_latency_s=0.2),
        _ev(0.6, "heartbeat", "failure", host=1),          # duplicate: once
        _ev(2.0, "heartbeat", "rejoin", host=1),
        _ev(2.1, "injector", "bitflip", step=5, leaf="params.w", bit=3),
        _ev(2.6, "injector", "bitflip", step=6, leaf="params.w", bit=9),
    ]
    sc = to_scenario(events)
    assert sc.clock == "time" and sc.name == "derived-replay"
    kills = sc.point_events("kill_hosts")
    assert len(kills) == 1 and kills[0].args["hosts"] == [1]
    assert kills[0].at == pytest.approx(0.5)
    assert sc.point_events("rejoin")[0].at == pytest.approx(2.0)
    storms = sc.window_events("sdc_storm")
    assert len(storms) == 1
    assert storms[0].args["leaves"] == ["params.w"]
    assert storms[0].at == pytest.approx(2.1)
    rep = ControlPlaneSim(4, period=0.1).run(sc)
    assert {d["host"] for d in rep.detections} == {1}
    assert sorted(h for _, hs in rep.grow_events for h in hs) == [1]


# ---------------------------------------------------------------------------
# Observability bundle
# ---------------------------------------------------------------------------


def test_observability_snapshot_and_dump(tmp_path):
    obs = Observability(capacity=100)
    obs.emit("heartbeat", "failure", host=2)
    obs.emit("elastic", "resume", step=4)
    obs.registry.counter("heartbeat.failures").inc()
    snap = obs.snapshot()
    assert snap["events"] == {"retained": 2, "emitted": 2, "dropped": 0}
    assert snap["timeline"]["incidents"] == 1
    assert snap["metrics"]["heartbeat.failures"] == 1
    out = str(tmp_path / "tele")
    paths = obs.dump(out)
    # no sink was attached: dump back-fills the retained ring
    assert len(load_jsonl(paths["events"])) == 2
    with open(paths["trace"]) as f:
        assert json.load(f)["otherData"]["summary"]["closed"] == 1
    with open(paths["metrics_json"]) as f:
        assert json.load(f)["heartbeat.failures"] == 1
    with open(paths["metrics_prom"]) as f:
        assert "heartbeat_failures 1" in f.read()
    # a second dump with the sink now attached reuses the live log
    obs.emit("s", "more")
    assert obs.dump(out)["events"] == paths["events"]
    assert len(load_jsonl(paths["events"])) == 3
    obs.close()


# ---------------------------------------------------------------------------
# live integration: heartbeat latency, Young/Daly feedback, serve back-compat
# ---------------------------------------------------------------------------


def test_heartbeat_monitor_records_detection_latency():
    from repro.core import HeartbeatEmitter, HeartbeatMonitor
    obs = Observability()
    period = 0.05
    detected = threading.Event()
    mon = HeartbeatMonitor(num_hosts=2, period=period, timeout_factor=4.0,
                           on_failure=lambda h: detected.set(),
                           obs=obs).start()
    ems = [HeartbeatEmitter(i, mon.addr, period).start() for i in range(2)]
    time.sleep(8 * period)                    # establish liveness
    ems[1].pause()
    assert detected.wait(5.0)
    lat = mon.detection_latency[1]
    # declared after ~timeout (4 periods) from the last accepted beat
    assert 0.0 < lat < 2.0
    evs = obs.events(subsystem="heartbeat", kind="failure")
    assert evs and evs[0].data["host"] == 1
    assert evs[0].data["detection_latency_s"] == pytest.approx(lat)
    h = obs.registry.histogram("heartbeat.detection_latency_ms", host=1)
    assert h.count == 1 and h.p50 == pytest.approx(lat * 1e3)
    assert obs.registry.counter("heartbeat.failures").value == 1
    for e in ems:
        e.stop()
    mon.stop()


def test_policy_observe_recovery_adapts_young_daly_terms():
    from repro.core.policy import CheckpointPolicy, SystemModel
    pol = CheckpointPolicy(mode="young_daly",
                           system=SystemModel(restart_seconds=120.0,
                                              downtime_seconds=60.0),
                           ema=0.7)
    pol.observe_recovery(restart_s=2.0, downtime_s=0.5)
    assert pol.system.restart_seconds == pytest.approx(0.7 * 120 + 0.3 * 2)
    assert pol.system.downtime_seconds == pytest.approx(0.7 * 60 + 0.3 * 0.5)
    before = pol.system.restart_seconds
    pol.observe_recovery(downtime_s=0.5)      # partial update: R untouched
    assert pol.system.restart_seconds == before
    # repeated measurements converge on the measured value
    for _ in range(60):
        pol.observe_recovery(restart_s=2.0, downtime_s=0.5)
    assert pol.system.restart_seconds == pytest.approx(2.0, rel=1e-3)
    assert pol.system.downtime_seconds == pytest.approx(0.5, rel=1e-3)


def test_serve_engine_events_backcompat_via_bus():
    """``ServeEngine.events`` is now a view over the shared bus: same
    ``{"t", "step", "event", ...}`` dicts as the old list, same data, and
    the same handle also feeds the engine's latency histograms."""
    import jax
    from repro.core import FaultInjector
    from repro.models import get_config, init_params
    from repro.serve import ServeEngine
    cfg = get_config("granite-3-8b", tiny=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    obs = Observability()
    inj = FaultInjector()
    inj.schedule_replica_kill(2, replica_id=1)
    eng = ServeEngine(cfg, params, num_replicas=2, slots_per_replica=2,
                      max_len=12, fault_tolerant=True,
                      heartbeat_period=0.05, heartbeat_timeout_factor=40.0,
                      fault_injector=inj, obs=obs)
    assert eng.obs is obs                     # shared, not engine-private
    rids = [eng.submit([1, 2, 3, 4], 4) for _ in range(3)]
    results = eng.run()
    assert len(results) == len(rids)
    evs = eng.events
    assert evs, "the failover must have recorded lifecycle events"
    assert all(set(e) >= {"t", "step", "event"} for e in evs)
    assert any(e["event"] == "replica_failed" for e in evs)
    assert [e.kind for e in obs.events(subsystem="serve")] \
        == [e["event"] for e in evs]
    assert obs.registry.counter("serve.replica_failures").value == 1
    assert obs.registry.histogram("serve.latency_ms").count == len(rids)
    assert obs.registry.counter("serve.requests_done").value == len(rids)
    assert obs.registry.counter("serve.tokens").value >= 4
    eng.shutdown()
    # an engine built without a handle still owns one (back-compat)
    eng2 = ServeEngine(cfg, params, num_replicas=1, slots_per_replica=2,
                      max_len=12, fault_tolerant=False)
    assert eng2.obs is not None and eng2.events == []
    eng2.shutdown()


def test_train_driver_history_rides_the_bus():
    """With obs attached the per-step records live on the bus; history()
    still merges newest-per-step, and records that fell off a small ring
    are recovered from the driver's local dict."""
    from repro.chaos import Scenario, TrainScenarioDriver
    obs = Observability(capacity=3)
    d = TrainScenarioDriver(Scenario("s"), settle_seconds=0, obs=obs)
    for step in range(6):
        d.on_metrics(step, {"step": step, "loss": 1.0 - step / 10})
    d.on_metrics(2, {"step": 2, "loss": 0.55})      # replay overwrites
    hist = d.history()
    assert [h["step"] for h in hist] == [0, 1, 2, 3, 4, 5]
    assert hist[2]["loss"] == 0.55
