"""The measured window: open-loop arrivals into ``BatchScheduler.run()``.

The scheduler admits only at segment boundaries, and its one callback
there is the ``chaos`` hook's ``tick(sched, segment)``, which runs after
every retire step.  The driver is that hook: at each tick it records the
tokens each request has received and submits every arrival now due.
``run()`` returns when its queue is empty and no row is active; the
driver then sleeps until the next due time and calls it again (a
re-entry, which starts a fresh pool, so the prefix cache is lost).

The schedule's lead-in (arrivals due before the window opens) is offered
first, so the window opens on a server at its steady load.  Lead-in
requests are served and their tokens delivered inside the window count,
but they are not among the requests the window measures.

Every time is counted from when the request was due.  The driver wraps
the engine's entry points on this one engine instance, to time them on
the host clock and to name them in the profiler's trace; what the
program computes is unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve import BatchScheduler, Request

from chipbench.traffic import Arrival


class DrainLimit(Exception):
    """Raised from a tick once the drain limit has passed."""


@dataclasses.dataclass
class Rec:
    """What the host saw of one request (seconds on ``perf_counter``)."""
    rid: int
    due: float
    prompt_len: int
    budget: int
    measured: bool                      # due in the window, not lead-in
    admitted: Optional[float] = None    # its slot prefill was called
    first: Optional[float] = None       # tick that delivered token 1
    last: Optional[float] = None        # tick that delivered the last
    tokens: int = 0
    tokens_in_window: int = 0
    done: bool = False


class Driver:
    def __init__(self, eng, arrivals: Sequence[Arrival], seconds: float,
                 drain_s: float, trace=None, lead_s: float = 0.0):
        self.eng = eng
        self.arrivals = list(arrivals)
        self.measured = [a for a in self.arrivals if a.measured]
        self.lead_s = float(lead_s)
        self.seconds = float(seconds)
        self.drain_s = float(drain_s)
        self.trace = trace              # a trace.Tracer, or None
        self.recs: Dict[int, Rec] = {}
        self.reentries = 0
        self.prefills: List[tuple] = []     # (t, tokens, prefix_len)
        self.segments: List[tuple] = []     # (t, steps, active ctx lens)
        self.cows = 0
        self._next = 0
        self._admitted = 0
        self._completed = 0
        self.sched = BatchScheduler(eng, chaos=self)
        self._wrap()

    # ------------------------------------------------------------ wrappers
    def _wrap(self) -> None:
        eng, sched = self.eng, self.sched
        prefill_slot, copy_pages = eng.prefill_slot, eng.copy_pages
        decode_segment, fetch = eng.decode_segment, eng._fetch
        set_page_table, pick = eng.set_page_table, sched._pick_admission

        def prefill(state, logits, prompt, slot, table_row=None,
                    prefix_len=0):
            self.prefills.append((time.perf_counter(), len(prompt),
                                  prefix_len))
            with TraceAnnotation("prefill_slot"):
                return prefill_slot(state, logits, prompt, slot,
                                    table_row=table_row,
                                    prefix_len=prefix_len)

        def cow(state, pairs):
            self.cows += len(pairs)
            with TraceAnnotation("cow_copy"):
                return copy_pages(state, pairs)

        def segment(steps):
            fn = decode_segment(steps)
            q = eng.quantize_steps(steps)

            def call(*args):
                lens = np.array([sched._slot_len[i]
                                 for i, r in enumerate(sched._slots)
                                 if r is not None], np.int64)
                self.segments.append((time.perf_counter(), q, lens))
                with TraceAnnotation("segment"):
                    return fn(*args)
            return call

        def fetch_(tree):
            with TraceAnnotation("fetch"):
                return fetch(tree)

        def page_table(state, table):
            with TraceAnnotation("page_table"):
                return set_page_table(state, table)

        def admission():
            with TraceAnnotation("admission"):
                return pick()

        eng.prefill_slot, eng.copy_pages = prefill, cow
        eng.decode_segment, eng._fetch = segment, fetch_
        eng.set_page_table, sched._pick_admission = page_table, admission

    def unwrap(self) -> None:
        """Give the engine and scheduler their own entry points back."""
        for name in ("prefill_slot", "copy_pages", "decode_segment",
                     "_fetch", "set_page_table"):
            self.eng.__dict__.pop(name, None)
        self.sched.__dict__.pop("_pick_admission", None)

    # ---------------------------------------------------------------- run
    def _submit_due(self, now: float) -> None:
        while (self._next < len(self.arrivals)
               and self.t0 + self.arrivals[self._next].due_s <= now):
            a = self.arrivals[self._next]
            self._next += 1
            self.recs[a.rid] = Rec(rid=a.rid, due=self.t0 + a.due_s,
                                   prompt_len=len(a.prompt), budget=a.budget,
                                   measured=a.measured)
            self.sched.submit(Request(rid=a.rid, prompt=list(a.prompt),
                                      max_new_tokens=a.budget))

    def _deliver(self, req: Request, t: float) -> None:
        rec = self.recs[req.rid]
        n = len(req.generated)
        if n > rec.tokens:
            if not rec.tokens:
                rec.first = t
            rec.last = t
            if self.t0 <= t <= self.t_end:
                rec.tokens_in_window += n - rec.tokens
            rec.tokens = n
        rec.done = req.status == "done" and n == rec.budget

    def tick(self, sched, segment: int) -> None:
        with TraceAnnotation("tick"):
            t = time.perf_counter()
            log = sched.admission_log
            while self._admitted < len(log):
                rid, _slot = log[self._admitted]
                self.recs[rid].admitted = self.prefills[self._admitted][0]
                self._admitted += 1
            for req in sched._slots:
                if req is not None:
                    self._deliver(req, t)
            done = list(sched.completed.values())
            for req in done[self._completed:]:
                self._deliver(req, t)
            self._completed = len(done)
            if self.trace is not None:
                self.trace.at_tick(t)
            self._submit_due(time.perf_counter())
            if t > self.t_end + self.drain_s:
                raise DrainLimit()

    def run(self) -> None:
        """Offer the lead-in from now, then the window's arrivals for
        ``seconds``, then follow every request due in the window to its
        end or to the drain limit."""
        self.t0 = time.perf_counter() + self.lead_s
        self.t_end = self.t0 + self.seconds
        if self.trace is not None:
            self.trace.t0 = self.t0
        calls = 0
        try:
            while True:
                now = time.perf_counter()
                self._submit_due(now)
                if not self.sched.queue:
                    if self._next >= len(self.arrivals):
                        break
                    due = self.t0 + self.arrivals[self._next].due_s
                    with TraceAnnotation("idle_sleep"):
                        time.sleep(max(due - time.perf_counter(), 0.0))
                    continue
                calls += 1
                with TraceAnnotation("run"):
                    self.sched.run()
        except DrainLimit:
            pass
        self.stopped = time.perf_counter()
        self.reentries = max(calls - 1, 0)
        if self.trace is not None:
            self.trace.at_end()

    # ------------------------------------------------------------ results
    def attempted(self) -> int:
        return len(self.measured)

    def failed(self) -> int:
        return sum(1 for a in self.measured
                   if a.rid not in self.recs or not self.recs[a.rid].done)
