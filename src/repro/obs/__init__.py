"""Dependability telemetry: structured events, live metrics, failure
timelines, record-and-replay (docs/observability.md).

``Observability`` bundles the event bus and the metrics registry behind
one handle that every layer shares::

    obs = Observability(jsonl_path="telemetry/events.jsonl")
    dep.attach_obs(obs)              # training plane
    engine = ServeEngine(..., obs=obs)   # serving plane

    obs.emit("heartbeat", "failure", host=3)
    obs.registry.counter("sdc.detected", tier="abft").inc()
    with obs.span("data.batch"):     # histogram + span log + profiler
        batch = data.next_batch()

    obs.timeline().summary()         # {"mttr_s": ..., "availability": ...}
    obs.to_scenario()                # recorded log -> replayable Scenario
    obs.dump("out/telemetry")        # events.jsonl + trace.json (events
                                     # and spans) + metrics.json/.prom
"""
from __future__ import annotations

import os
from typing import Any, List, Optional

from repro.obs.bus import DEFAULT_CAPACITY, Event, EventBus, load_jsonl
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               Span, SpanLog, span)
from repro.obs.timeline import Incident, Timeline
from repro.obs.export import (to_chrome_trace, to_scenario,
                              write_chrome_trace)
from repro.obs.anomaly import (AnomalyEngine, BeatJitterDetector,
                               ScrubRateDetector, StepTimeDriftDetector,
                               make_proactive_hook)
from repro.obs.agent import TelemetryAgent
from repro.obs.collector import Collector

__all__ = [
    "Observability", "EventBus", "Event", "DEFAULT_CAPACITY",
    "load_jsonl", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "Span", "SpanLog", "span", "Timeline", "Incident", "to_chrome_trace",
    "write_chrome_trace", "to_scenario", "AnomalyEngine",
    "BeatJitterDetector", "ScrubRateDetector", "StepTimeDriftDetector",
    "make_proactive_hook", "TelemetryAgent", "Collector",
]


class Observability:
    """Event bus + metrics registry, one per deployment (process)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 jsonl_path: Optional[str] = None):
        self.bus = EventBus(capacity=capacity)
        self.registry = MetricsRegistry()
        if jsonl_path is not None:
            self.bus.attach_jsonl(jsonl_path)

    # -- producing -----------------------------------------------------
    def emit(self, subsystem: str, kind: str, **data: Any) -> Event:
        return self.bus.emit(subsystem, kind, **data)

    def span(self, name: str) -> Span:
        """Time a block as ``name`` (``<layer>.<what>``): its milliseconds
        into the histogram of that name, its interval and thread into
        ``registry.spans``, and a profiler annotation while it runs."""
        return self.registry.span(name)

    # -- derived views -------------------------------------------------
    def events(self, subsystem: Optional[str] = None,
               kind: Optional[str] = None) -> List[Event]:
        return self.bus.events(subsystem=subsystem, kind=kind)

    def timeline(self) -> Timeline:
        return Timeline.from_events(self.bus.events())

    def to_scenario(self, name: Optional[str] = None):
        return to_scenario(self.bus.events(), name=name)

    def snapshot(self) -> dict:
        """Metrics + timeline summary, JSON-ready."""
        return {"metrics": self.registry.snapshot(),
                "timeline": self.timeline().summary(),
                "events": {"retained": len(self.bus),
                           "emitted": self.bus.total_emitted,
                           "dropped": self.bus.dropped}}

    # -- persistence ---------------------------------------------------
    def dump(self, out_dir: str) -> dict:
        """Write the full telemetry bundle under ``out_dir``; returns the
        path map.  If no JSONL sink was attached, the retained ring is
        written out instead (bounded history)."""
        os.makedirs(out_dir, exist_ok=True)
        paths = {}
        evs = self.bus.events()
        if self.bus._jsonl_path is None:
            jsonl = os.path.join(out_dir, "events.jsonl")
            self.bus.attach_jsonl(jsonl)
            # back-fill the retained ring into the fresh sink
            import json as _json
            with self.bus._lock:
                sink = self.bus._jsonl
            for ev in evs:
                sink.write(_json.dumps(ev.to_dict()) + "\n")
            paths["events"] = jsonl
        else:
            paths["events"] = self.bus._jsonl_path
        self.bus.flush()
        paths["trace"] = write_chrome_trace(
            os.path.join(out_dir, "trace.json"), evs, self.timeline(),
            spans=self.registry.spans.records())
        paths["metrics_json"] = os.path.join(out_dir, "metrics.json")
        self.registry.to_json(paths["metrics_json"])
        paths["metrics_prom"] = os.path.join(out_dir, "metrics.prom")
        with open(paths["metrics_prom"], "w") as f:
            f.write(self.registry.to_prometheus())
        return paths

    def close(self) -> None:
        self.bus.close()
