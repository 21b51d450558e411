"""Tier-2 SDC detection: the rotating state scrubber.

Training state only changes legitimately inside ``train_step``; between the
end of one superstep and the start of the next, every leaf should be
bit-identical.  The scrubber exploits that window: ``record(state, step)``
checksums a rotating subset of leaves right after the update, and
``verify(state)`` recomputes those checksums just before the next update
consumes the state — any difference is memory corruption, pinpointed to
the leaf.  With ``fraction=f`` each call checksums ceil(f * num_leaves)
leaves, so a full-state scrub is amortized over 1/f steps (f=1 covers
every leaf every step; the bench quantifies the cost curve).

With an ``Observability`` (``obs``, handed over by
``Dependability.attach_obs``) each ``record`` and ``verify`` opens three
spans: ``sdc.leaves`` (naming the leaves and choosing the subset),
``sdc.reduce`` (dispatching the checksum program) and ``sdc.fetch``
(waiting for its result and comparing), and counts the bytes checksummed
(``sdc.checksummed_bytes``).

The scrubber is windowed, not historical: only the most recent record is
verifiable, because older baselines predate legitimate updates.  Coverage
is therefore probabilistic for f < 1 — a flip in an un-scrubbed leaf rides
until the tier-3 sentinel (or an ABFT matmul) notices its effect.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

from repro.obs.metrics import span
from repro.sdc.checksum import checksums, launch, named_leaves


class StateScrubber:
    def __init__(self, fraction: float = 0.25):
        if not 0.0 < fraction <= 1.0:
            raise ValueError(f"fraction must be in (0, 1], got {fraction}")
        self.fraction = fraction
        self._cursor = 0
        self._window: Dict[str, int] = {}    # leaf name -> checksum
        self._window_step: Optional[int] = None
        self.obs = None                      # repro.obs.Observability

    # ------------------------------------------------------------------
    def _subset(self, names: List[str]) -> List[str]:
        n = len(names)
        k = max(1, math.ceil(n * self.fraction))
        picked = [names[(self._cursor + i) % n] for i in range(min(k, n))]
        self._cursor = (self._cursor + k) % n
        return picked

    def record(self, state, step: int) -> List[str]:
        """Checksum the next rotation subset of ``state``; returns the
        covered leaf names.  Call right after the state is produced."""
        with span(self.obs, "sdc.leaves"):
            leaves = dict(named_leaves(state))
            subset = self._subset(sorted(leaves))
        fetch = launch([leaves[n] for n in subset], self.obs)
        with span(self.obs, "sdc.fetch"):
            self._window = dict(zip(subset, fetch()))
            self._window_step = step
        return subset

    def verify(self, state) -> List[str]:
        """Re-checksum the recorded window against ``state``; returns the
        names of corrupted leaves (empty = clean).  Call before the next
        update consumes the state."""
        if not self._window:
            return []
        with span(self.obs, "sdc.leaves"):
            leaves = dict(named_leaves(state))
            names = [n for n in self._window if n in leaves]
        fetch = launch([leaves[n] for n in names], self.obs)
        with span(self.obs, "sdc.fetch"):
            return [n for n, g in zip(names, fetch())
                    if g != self._window[n]]

    def full_checksums(self, state) -> Dict[str, int]:
        """Checksum every leaf (save-time verification / debugging)."""
        named = named_leaves(state)
        return dict(zip((n for n, _ in named),
                        checksums([v for _, v in named])))

    def reset(self) -> None:
        """Drop the window (call after a rollback: the restored state is a
        different set of buffers than the recorded one)."""
        self._window = {}
        self._window_step = None
