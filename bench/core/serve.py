"""Drives the serving engine through warm-up and the measured window.

Every token is stamped on the benchmark's clock when ``step()`` returns
it, and the same clock is passed as ``now``.  In an open loop a request's
latency runs from when it was due, so the wait that a slow iteration puts
on later arrivals counts.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class ReqLog:
    idx: int
    due: float                      # on the bench clock
    n_prompt: int
    max_new: int
    rid: int = -1
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)   # return time of each token
    admit: float | None = None      # start of the step that admitted it

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclass
class StepLog:
    start: float
    end: float
    tokens: int                     # tokens returned
    launches: int                   # decode launches in the step
    prefills: list                  # prompt lengths admitted
    decode_ctx: list                # context length of each decoded token


class CompileLog:
    """Backend compiles as they happen (``jax.monitoring``): the clock
    time each one ended and its seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, clock):
        import jax
        self.events: list[tuple[float, float]] = []

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.events.append((clock(), float(duration)))
        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0: float, t1: float) -> list[tuple[float, float]]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class Loop:
    """One engine and one list of generated requests."""

    def __init__(self, engine, requests, clock, spans: bool = False):
        self.engine = engine
        self.clock = clock
        self.logs = [ReqLog(r.idx, r.due, len(r.prompt), r.max_new)
                     for r in requests]
        self._prompts = [r.prompt for r in requests]
        self.by_rid: dict[int, ReqLog] = {}
        self.steps: list[StepLog] = []
        self.next = 0               # first request not yet submitted
        self.spans = spans

    def span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit_due(self, now: float) -> None:
        """Submit every request due by ``now``."""
        with self.span("bench.submit"):
            while self.next < len(self.logs) and self.logs[self.next].due <= now:
                log = self.logs[self.next]
                log.rid = self.engine.submit(self._prompts[self.next].tolist(),
                                             log.max_new, now=log.due)
                self.by_rid[log.rid] = log
                self.next += 1

    def step(self) -> StepLog:
        launches0 = self.engine.kernel_calls
        t0 = self.clock()
        with self.span("bench.step"):
            out = self.engine.step(t0)
        t1 = self.clock()
        prefills, ctx = [], []
        for rid, tok in out:
            log = self.by_rid[rid]
            k = len(log.tokens)
            if k == 0:
                log.admit = t0
                prefills.append(log.n_prompt)
            else:
                ctx.append(log.n_prompt + k)
            log.tokens.append(int(tok))
            log.times.append(t1)
        s = StepLog(t0, t1, len(out), self.engine.kernel_calls - launches0,
                    prefills, ctx)
        self.steps.append(s)
        return s

    def wait_until(self, t: float) -> None:
        with self.span("bench.wait"):
            while self.clock() < t:
                time.sleep(min(0.002, max(0.0, t - self.clock())))

    def run_open(self, until: float) -> None:
        """Open loop until the first step that ends at or after ``until``."""
        while True:
            now = self.clock()
            if now >= until:
                return
            self.submit_due(now)
            if self.engine.idle:
                nxt = (self.logs[self.next].due if self.next < len(self.logs)
                       else until)
                self.wait_until(min(nxt, until))
            else:
                self.step()

    def run_steps(self, until: float) -> None:
        """Closed queue: step until the first step that ends at or after
        ``until``."""
        while self.clock() < until:
            if self.engine.idle:
                raise RuntimeError("the offline queue ran dry inside the "
                                   "window; the traffic needs more requests")
            self.step()


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of all values."""
    if not len(values):
        raise ValueError("no sample: nothing fell in the window")
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_latencies(logs, t0: float, t1: float) -> dict:
    """TTFT, queue wait and inter-token gaps, in seconds, of the requests
    due in ``[t0, t1)``.  A request with no first token by ``t1`` counts
    at ``t1 - due``, and one not yet admitted waits ``t1 - due``: none is
    dropped.  Gaps are those between consecutive tokens returned inside
    the window, of every request."""
    ttft, wait, itl = [], [], []
    for log in logs:
        if t0 <= log.due < t1:
            ttft.append((log.times[0] if log.times and log.times[0] <= t1
                         else t1) - log.due)
            wait.append((log.admit if log.admit is not None and log.admit <= t1
                         else t1) - log.due)
        ts = [t for t in log.times if t0 <= t <= t1]
        itl.extend(np.diff(ts).tolist())
        if ts and not log.done:         # still waiting for its next token
            itl.append(t1 - ts[-1])
    return {"ttft": ttft, "wait": wait, "itl": itl}
