"""The harness's own clock on the serve loop.

``Engine.serve`` runs one host loop over a ``SlotScheduler``. While the
window runs, this module wraps two of the scheduler's public methods at class
level and restores them afterwards:

* ``advance(t)`` starts every loop iteration and moves arrived requests into
  the queue: a request is due at the first ``advance`` that queues it;
* ``slot_done(slot)`` is called right after every token a lane emits (the
  first token at admission, then one per decode step): the time of that call
  is the token's emission time.

The wrappers only read scheduler state and the host clock. In a traced run
they also write a profiler span per admission round (``host:admission``),
through ``admit``, so that the trace reduction can attribute device idle
time to it.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List

from repro.serving.scheduler import SlotScheduler


class Recorder:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.due: Dict[int, float] = {}
        self.emits: Dict[int, List[float]] = {}

    @contextlib.contextmanager
    def attached(self, spans: bool = False):
        cls = SlotScheduler
        orig = {n: getattr(cls, n) for n in ("advance", "slot_done", "admit")}
        rec = self

        def advance(sched, t):
            orig["advance"](sched, t)
            now = rec.clock()
            for r in sched.queue:
                rec.due.setdefault(r.rid, now)

        def slot_done(sched, slot):
            st = sched.slots[slot]
            if st is not None:
                ws = rec.emits.setdefault(st.request.rid, [])
                n = len(st.generated)
                if n > len(ws):
                    ws.extend([rec.clock()] * (n - len(ws)))
            return orig["slot_done"](sched, slot)

        def admit(sched, t=0.0):
            import jax

            gen = orig["admit"](sched, t)
            while True:
                with jax.profiler.TraceAnnotation("host:admission"):
                    try:
                        item = next(gen)
                    except StopIteration:
                        return
                    yield item

        cls.advance, cls.slot_done = advance, slot_done
        if spans:
            cls.admit = admit
        try:
            yield self
        finally:
            for n, f in orig.items():
                setattr(cls, n, f)
