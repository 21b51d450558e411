"""Reduce a profiler trace to device busy time, per-op device time and
idle gaps attributed to what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each chip
is a plane ``/device:TPU:<n>``.  On it the line ``XLA Ops`` gives the
intervals in which single operations ran, each named by its HLO text, and
the line ``XLA Modules`` the programs they belong to.  An op is named here
``<program>/<instruction>``, e.g. ``jit_train_step/%fusion.9``, with the
program's id left out, so that the name stays the same from run to run.
Host planes hold one line per thread; the thread that carries the
benchmark's ``bench.window`` span is the one whose spans name each gap.
Spans are named ``<layer>.<what>`` in lower case (``ckpt.save``); other
host events on that line (the runtime's, the Python tracer's) name none.

Everything is in seconds.  Only the part of the trace inside the
``bench.window`` span counts.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
# the scrubber's checksum program (``sdc/checksum.py`` ``_device_sums``):
# the storage words of each leaf and their weighted sum, in XLA's own ops
CHECKSUM_PROGRAM = "jit__device_sums"

Interval = Tuple[str, float, float]


def load(trace_dir: str) -> Dict:
    """{"devices": {plane: [(op, start, end)]}, "host": [(span, start,
    end)]} from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def _instruction(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def from_profile(pd) -> Dict:
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if TPU_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9,
                           _program(e.name))
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(OPS_LINE, []):
                a, b = e.start_ns * 1e-9, e.end_ns * 1e-9
                i = bisect.bisect_right(starts, a) - 1
                prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
                ops.append((f"{prog}/{_instruction(e.name)}", a, b))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                       for e in ln.events]
                if any(n == WINDOW for n, _, _ in evs):
                    host = [ev for ev in evs if SPAN.match(ev[0])]
    return {"devices": devices, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_of(tr: Dict) -> Tuple[float, float]:
    spans = [(a, b) for n, a, b in tr["host"] if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return spans[-1]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _host_at(host: List[Interval], points: List[float]) -> List[str]:
    """Innermost host span open at each of the sorted ``points``.  The
    spans of one thread nest, so a stack of the open ones is enough."""
    evs = sorted(host, key=lambda e: (e[1], -e[2]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(evs) and evs[i][1] <= t:
            while stack and stack[-1][2] <= evs[i][1]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else "(none)")
    return out


def reduce(tr: Dict, top: int = 10) -> Dict:
    """busy_s (mean over devices), window_s, op_s (op name -> device
    seconds, mean over devices), program_s (program -> seconds in which
    any of its ops ran, mean over devices), device_ops (the ``top``
    longest ops by total time) and idle_gaps (device idle seconds in the
    window, summed by the host span open in each gap, the ``top``
    largest)."""
    lo, hi = window_of(tr)
    n_dev = max(len(tr["devices"]), 1)
    busy = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []           # (midpoint, seconds)
    for evs in tr["devices"].values():
        by_prog: Dict[str, list] = defaultdict(list)
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_s[name] += (b - a) / n_dev
                by_prog[name.split("/", 1)[0]].append((a, b))
        for prog, iv in by_prog.items():
            program_s[prog] += sum(b - a for a, b in union(iv)) / n_dev
        u = union(_clip([(a, b) for _, a, b in evs], lo, hi))
        busy += sum(b - a for a, b in u) / n_dev
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [((a + b) / 2, (b - a) / n_dev)
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort()
    idle: Dict[str, float] = defaultdict(float)
    for name, (_, s) in zip(_host_at(tr["host"], [t for t, _ in gaps]),
                            gaps):
        idle[name] += s
    rank = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "window_s": hi - lo, "op_s": dict(op_s),
            "program_s": dict(program_s), "device_ops": rank(op_s),
            "idle_gaps": rank(idle)}


def idle_share(red: Dict) -> float:
    """Share of the window, %, in which no op ran (mean over devices)."""
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
