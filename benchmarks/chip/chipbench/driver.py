"""Wall-clock driver of the program's scheduler and engine.

It makes the calls the program's own loop (``serve_online``) makes, in its
order — ``scheduler.submit`` when a request is due, ``scheduler.next_plan``
with the admission and preemption hooks, the executor on the plan,
``scheduler.on_tokens`` with the release hook — but on the host's clock:
requests are due at fixed times after the traffic starts, the driver
sleeps while nothing is due, and every token is stamped when the executor
returns it to the host.

Each call is a span: ``bench.next_plan#k``, ``bench.execute#k``,
``bench.on_tokens#k`` for step ``k``, and ``bench.wait`` while idle.
With ``annotate`` they are ``jax.profiler.TraceAnnotation``s, so a traced
run finds them on the device trace's clock.
"""
from __future__ import annotations

import contextlib
import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench.traffic import Job

clock = time.perf_counter


@dataclass
class Log:
    """What happened to one request, on the host's clock."""
    job: Job
    due: float                          # absolute
    submitted: Optional[float] = None
    tokens: List[float] = field(default_factory=list)
    finished: Optional[float] = None
    rejected: bool = False
    output: List[int] = field(default_factory=list)


@dataclass
class Step:
    """One plan, as the scheduler composed it and the engine ran it."""
    idx: int
    plan: Tuple[float, float]
    execute: Tuple[float, float]
    on_tokens: Tuple[float, float]
    chunks: List[Tuple[int, int, bool]]     # (start, length, is_last)
    decodes: List[int]                      # context of each real decode


class Driver:
    def __init__(self, scheduler, executor, *, annotate: bool = False):
        self.sched = scheduler
        self.ex = executor
        self.annotate = annotate
        self.logs: Dict[int, Log] = {}
        self.steps: List[Step] = []
        self.n_preemptions = 0
        self._pending: list = []            # heap of (due, req_id, job)
        self._t0 = 0.0
        self._n_rejected = 0

    # ------------------------------------------------------------ traffic
    def offer(self, job: Job, due: float):
        heapq.heappush(self._pending, (due, job.req_id, job))

    def open_loop(self, jobs: List[Job], t0: float):
        self._t0 = t0
        for j in jobs:
            self.offer(j, t0 + j.due)

    # -------------------------------------------------------------- hooks
    def _span(self, name: str):
        if self.annotate:
            import jax
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    def _admit(self, req):
        self.ex.admit(req)

    def _preempt(self, req):
        self.ex.preempt(req)
        self.n_preemptions += 1

    def _release(self, req):
        self.ex.release(req)
        now = clock()
        log = self.logs[req.req_id]
        log.finished = now
        log.output = list(req.output)

    # --------------------------------------------------------------- loop
    def _submit_due(self, now: float):
        from repro.scheduler import Request
        while self._pending and self._pending[0][0] <= now:
            due, _, job = heapq.heappop(self._pending)
            self.logs[job.req_id] = Log(job=job, due=due, submitted=now)
            self.sched.submit(Request(
                prompt=job.prompt, max_new_tokens=job.max_new_tokens,
                req_id=job.req_id, arrival_time=due - self._t0))

    def _wait(self, until: float):
        with self._span("wait"):
            dt = until - clock()
            if dt > 0:
                time.sleep(dt)

    def _wake(self, t_end: float) -> float:
        """When the idle loop next has something to do."""
        return min(self._pending[0][0], t_end) if self._pending else t_end

    def run(self, t_end: float, until_idle: bool = False):
        """Serve until ``t_end`` (or, with ``until_idle``, until nothing is
        pending or running)."""
        k = len(self.steps)
        while True:
            now = clock()
            if now >= t_end or (until_idle and not self._pending
                                and not self.sched.has_work):
                break
            self._submit_due(now)
            if not self.sched.has_work:
                self._wait(self._wake(t_end))
                continue
            a = clock()
            with self._span(f"next_plan#{k}"):
                plan = self.sched.next_plan(
                    admit_hook=self._admit, now=now - self._t0,
                    preempt_hook=self._preempt)
            b = clock()
            for req in self.sched.rejected[self._n_rejected:]:
                log = self.logs[req.req_id]
                log.rejected, log.finished = True, b
                self._n_rejected += 1
            if plan is None:
                self._wait(min(self._wake(t_end), b + 1e-3))
                continue
            with self._span(f"execute#{k}"):
                tokens, _ = self.ex(plan)
            c = clock()
            for rid in tokens:
                self.logs[rid].tokens.append(c)
            with self._span(f"on_tokens#{k}"):
                self.sched.on_tokens(tokens, release_hook=self._release)
            d = clock()
            self.steps.append(Step(
                idx=k, plan=(a, b), execute=(b, c), on_tokens=(c, d),
                chunks=[(ch.start, len(ch.tokens), ch.is_last)
                        for ch in plan.chunks],
                decodes=[w.ctx for w in plan.decodes]))
            k += 1
