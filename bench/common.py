"""What every driver shares: the seed, the configuration as run, the device
record, set-up marks, compile events, host spans and the quartile spread.

Nothing here imports the program (``repro``): the drivers do that, so the
reference and the reductions stay independent of it.
"""
from __future__ import annotations

import contextlib
import json
import os
import statistics
import time
from typing import Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def as_run(conf: dict) -> dict:
    """A configuration as the program runs it: the file's published values,
    with the value each entry of ``departures`` gives in place of the
    published one (where the program has no option to run it)."""
    out = dict(conf)
    out.update({k: d["as_run"] for k, d in conf.get("departures", {}).items()})
    return out


def program_seed(seed: int) -> int:
    """A run's seed may exceed 32 bits; JAX keys keep only the low 32
    with x64 off, and the program's pipeline stores its seed as an int32.
    Fold it into [0, 2**31) so every seed reaches the program distinctly."""
    return int(seed) % (2 ** 31 - 1)


def use_checkout_cache() -> str:
    """JAX's persistent compile cache at the fixed ``<checkout>/.jax_cache``,
    whatever the environment names: the path is part of the cache's key,
    and a cache outside the checkout would be shared with other checkouts.
    Call before the first compile."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices`` (None where the
    backend keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peak_flops_bytes(device_kind: str) -> Tuple[float, float, float]:
    """(bf16 FLOP/s, HBM bytes/s, ICI bytes/s) of one chip from peaks.json;
    a device that is not in the table is an error, never a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    p = table[device_kind]
    return p["bf16_flops"], p["hbm_bytes_per_s"], p["ici_bits_per_s"] / 8


def mark(ctx: dict, name: str) -> None:
    """Note when a phase of set-up ended: (``time.perf_counter``,
    ``time.process_time``).  The run prints the marks beside its result,
    as wall and CPU seconds from process start, so a slow phase shows
    whether the host computed or waited."""
    ctx.setdefault("marks", {})[name] = (time.perf_counter(),
                                         time.process_time())


class CompileLog:
    """Keeps what JAX reports of its compiles inside a ``with`` block:
    tracing, lowering, backend compiles, and the persistent cache's hits,
    misses and retrieval time (``jax.monitoring`` events), each with the
    ``time.perf_counter`` at which it was reported."""

    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}
    SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
               "/jax/core/compile/backend_compile_duration": "compile_s",
               "/jax/compilation_cache/cache_retrieval_time_sec":
                   "cache_read_s"}

    def __init__(self):
        self.events: List[Tuple[float, str, float]] = []

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, event, **_):
        if event in self.COUNTS:
            self.events.append((time.perf_counter(), self.COUNTS[event], 1))

    def _duration(self, event, seconds, **_):
        if event in self.SECONDS:
            self.events.append((time.perf_counter(), self.SECONDS[event],
                                seconds))

    def totals(self, t0: float, t1: float) -> Dict[str, float]:
        """Each kind's sum over the events reported in [t0, t1)."""
        out = dict.fromkeys(list(self.COUNTS.values())
                            + list(self.SECONDS.values()), 0)
        for t, k, v in self.events:
            if t0 <= t < t1:
                out[k] += v
        return out


class Spans:
    """Host spans around calls into the program's layers.

    Each span is also a ``jax.profiler.TraceAnnotation``, so a traced run
    sees it on the host timeline beside the device ops and the trace
    reduction can name what the host was doing in an idle gap.  ``log``
    keeps (name, start, end) in ``time.perf_counter`` seconds."""

    def __init__(self):
        self.log: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.log.append((name, t0, time.perf_counter()))

    def wrap(self, obj, attr: str, name: str) -> None:
        """Replace ``obj.attr`` on the instance by a spanned call."""
        fn = getattr(obj, attr)

        def spanned(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        setattr(obj, attr, spanned)


def quartile_spread(values: List[float]) -> float:
    """Inter-quartile distance as a share of the median (Python's
    ``statistics.quantiles`` convention)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def checks_line(checks: Dict[str, Tuple[float, float]]) -> str:
    """'name=value (limit L)' for each compared number."""
    return "; ".join(f"{k}={v!r} (limit {lim!r})"
                     for k, (v, lim) in checks.items())
