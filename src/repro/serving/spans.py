"""Spans and counters of ``Engine.serve``'s host loop.

One :class:`LoopSpans` per serve call keeps the loop's clock and what it
counts, on ``time.perf_counter_ns`` (integer nanoseconds, so that sums are
exact):

* ``phase(name, **args)`` times one named stretch of host work. It opens the
  profiler span ``host:<name>`` with ``args`` as its stats, so that a device
  trace can book the device's idle time to it, and adds the phase's *self*
  time (its length less the phases nested in it) to the phase's seconds,
  count and longest span. Outside a profiler trace the span records nothing.
* ``tick()`` starts each loop iteration. The time no phase covers is
  ``loop``: the loop's own Python and its dispatches. The phases' self times
  and ``loop`` add up to ``wall_s``.
* ``queued`` / ``emitted`` / ``timings`` give each request's queue entry and
  token times on this one clock (``RequestResult.ttft_s``, ``tbt_s``,
  ``latency_s``).
* ``activated(kind)`` counts the admissions that produced a first token:
  ``full`` (the whole prompt prefilled) or ``tail`` (a prefix hit, only the
  tail prefilled). Each token compares the counts with those at its lane's
  previous token: the gap between the two is ``full`` if a full activation
  happened in it, else ``tail`` if a tail one did, else ``plain``. A lane's
  own activation comes before its first token, so it never counts in the
  lane's first gap.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax

PHASES = ("admission", "first_token", "step_sync", "emit", "swap_out",
          "resume")
GAP_KINDS = ("plain", "tail", "full")


class LoopSpans:
    def __init__(self, clock=time.perf_counter_ns):
        self._clock = clock
        self._t0 = clock()
        self._phases = {p: [0, 0, 0] for p in PHASES}  # ns, count, longest
        self._open: List[int] = []     # per open phase: ns of nested phases
        self._covered = 0              # self ns of every closed phase
        self._iter_at = (self._t0, 0)  # (clock, covered) at the last tick
        self._iters = 0
        self._longest_iter = 0         # ns outside phases, one iteration
        self._queued: Dict[int, int] = {}
        self._emits: Dict[int, List[int]] = {}
        self._acts: Tuple[int, int] = (0, 0)   # full, tail activations
        self._seen: Dict[int, Tuple[int, int]] = {}  # rid -> acts at its token
        self.gap_kinds = dict.fromkeys(GAP_KINDS, 0)

    def phase(self, name: str, **args) -> "_Phase":
        """Context manager: one stretch of host work named ``name``."""
        return _Phase(self, name, args)

    def _closed(self, name: str, took: int) -> None:
        own = took - self._open.pop()
        if self._open:
            self._open[-1] += took
        acc = self._phases[name]
        acc[0] += own
        acc[1] += 1
        if own > acc[2]:
            acc[2] = own
        self._covered += own

    def _close_iteration(self, now: int) -> None:
        at, covered = self._iter_at
        rest = (now - at) - (self._covered - covered)
        self._longest_iter = max(self._longest_iter, rest)
        self._iter_at = (now, self._covered)

    def tick(self) -> None:
        """Start of one loop iteration."""
        self._close_iteration(self._clock())
        self._iters += 1

    def now(self) -> int:
        return self._clock()

    def queued(self, rids: Iterable[int]) -> None:
        """Requests in the queue at this iteration; the first call that
        names a request is its queue entry."""
        now = self._clock()
        for rid in rids:
            self._queued.setdefault(rid, now)

    def activated(self, kind: str) -> None:
        full, tail = self._acts
        self._acts = (full + 1, tail) if kind == "full" else (full, tail + 1)

    def emitted(self, rid: int, at: Optional[int] = None) -> None:
        """One token of ``rid``, emitted now or at ``at`` (from ``now()``)."""
        self._emits.setdefault(rid, []).append(
            self._clock() if at is None else at)
        acts = self._acts
        last = self._seen.get(rid)
        if last == acts:
            self.gap_kinds["plain"] += 1
        elif last is not None:
            self.gap_kinds["full" if acts[0] != last[0] else "tail"] += 1
        self._seen[rid] = acts

    def timings(self, rid: int) -> Tuple[float, List[float], float]:
        """(ttft_s, tbt_s, latency_s) of a request that finishes now: queue
        entry to first token, each gap between tokens, queue entry to now."""
        now = self._clock()
        q0 = self._queued.pop(rid, self._t0)
        ws = self._emits.pop(rid, [])
        self._seen.pop(rid, None)
        return ((ws[0] - q0) * 1e-9 if ws else 0.0,
                [(b - a) * 1e-9 for a, b in zip(ws, ws[1:])],
                (now - q0) * 1e-9)

    def close(self) -> Tuple[float, Dict[str, List[float]]]:
        """(wall_s, host_s) at the end of the loop. ``host_s`` maps every
        phase, and ``loop`` for the rest, to [seconds, count, longest]; for
        ``loop`` the count is the loop's iterations and the longest is the
        most time one iteration spent outside phases."""
        now = self._clock()
        self._close_iteration(now)
        wall = now - self._t0
        host = {p: [ns * 1e-9, n, top * 1e-9]
                for p, (ns, n, top) in self._phases.items()}
        host["loop"] = [(wall - self._covered) * 1e-9, self._iters,
                        self._longest_iter * 1e-9]
        return wall * 1e-9, host


class _Phase:
    """The span and the clock of one ``LoopSpans.phase``."""

    __slots__ = ("spans", "name", "span", "start")

    def __init__(self, spans: LoopSpans, name: str, args: dict):
        self.spans, self.name = spans, name
        self.span = jax.profiler.TraceAnnotation("host:" + name, **args)

    def __enter__(self):
        self.span.__enter__()
        self.spans._open.append(0)
        self.start = self.spans._clock()

    def __exit__(self, *exc):
        self.spans._closed(self.name, self.spans._clock() - self.start)
        self.span.__exit__(*exc)
