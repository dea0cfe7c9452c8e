"""Drives the engine through a measured window as the clients see it.

Open loop: each request is submitted when it is due, whether or not the
server keeps up. Closed loop: each client submits its next request as
soon as its last one finishes. Every output token is stamped with the host
clock when the engine step that made it returns. After the window no new
request is sent and the engine runs until every request in flight is
done, so each request due in the window has its first token timed.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclass
class Served:
    index: int
    prompt: np.ndarray
    max_tokens: int
    due: float  # absolute host-clock seconds
    submit: float = 0.0
    rid: int = -1
    token_times: List[float] = field(default_factory=list)
    tokens: Optional[np.ndarray] = None  # set when finished


@dataclass
class Window:
    start: float
    end: float
    served: List[Served]
    step_spans: List[tuple]  # (start, end) of every engine step in the window
    late_s: List[float]  # submit time minus due time, open loop
    drained: bool  # every request finished
    closed_at: float  # when the run stopped stepping


def no_span(_name):
    return contextlib.nullcontext()


def run(engine, submit: Callable, requests, mix: dict, seconds: float,
        span: Callable = no_span, tick: Optional[Callable] = None,
        drain_limit_s: float = 60.0, clock=time.perf_counter,
        sleep=time.sleep) -> Window:
    """``submit(engine, prompt, max_tokens) -> rid``; ``span(name)`` a
    context manager for the host spans of a traced run; ``tick(now)`` is
    called once per turn of the loop, between engine steps."""
    start = clock()
    end = start + seconds
    served: Dict[int, Served] = {}
    order: List[Served] = []
    late: List[float] = []
    spans: List[tuple] = []
    queue = collections.deque(requests)
    closed = mix["loop"] == "closed"

    def send(req, due, now):
        with span("bench.submit"):
            s = Served(req.index, req.prompt, req.max_tokens, due, now)
            s.rid = submit(engine, req.prompt, req.max_tokens)
        served[s.rid] = s
        order.append(s)
        if not closed:
            late.append(now - due)

    def stamp(finished, now):
        for st in engine.scheduler.slots:
            if st is not None:
                _extend(served[st.req.rid], len(st.tokens), now)
        for f in finished:
            s = served[f.rid]
            _extend(s, len(f.tokens), now)
            s.tokens = np.asarray(f.tokens)
            if closed and now < end and queue:
                send(queue.popleft(), now, now)

    def _extend(s, n, now):
        s.token_times.extend([now] * (n - len(s.token_times)))

    if closed:
        for _ in range(mix["clients"]):
            send(queue.popleft(), start, start)
    while True:
        now = clock()
        if tick is not None:
            tick(now)
        if now >= end:
            break
        if not closed:
            while queue and start + queue[0].due <= now:
                req = queue.popleft()
                send(req, start + req.due, now)
        if engine.scheduler.pending():
            with span("bench.step"):
                t0 = clock()
                finished = engine.step()
                t1 = clock()
            spans.append((t0, t1))
            with span("bench.client"):
                stamp(finished, t1)
        elif not closed and queue:
            with span("bench.idle"):
                sleep(max(0.0, min(start + queue[0].due, end) - clock()))
        else:
            with span("bench.idle"):
                sleep(min(1e-3, max(0.0, end - clock())))
    # the clients send what fell due while the last step ran, and then
    # nothing more. The engine runs on until every request is done, or a
    # minute past the close once every request due in the window has its
    # first token (never more than two minutes past it)
    while not closed and queue and start + queue[0].due < end:
        req = queue.popleft()
        send(req, start + req.due, clock())
    while engine.scheduler.pending():
        now = clock()
        waiting = any(not s.token_times for s in order if s.due < end)
        if now > end + 2 * drain_limit_s or (
                now > end + drain_limit_s and not waiting):
            break
        finished = engine.step()
        stamp(finished, clock())
    return Window(start, end, order, spans, late,
                  drained=not engine.scheduler.pending(), closed_at=clock())


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(win: Window) -> dict:
    """tokens/s over the window, TTFT from due time of every request due
    in the window, and every gap between consecutive output tokens that
    ends in the window. A request due in the window that never got its
    first token has failed; its TTFT is counted as the time it waited."""
    due_in = [s for s in win.served if s.due < win.end]
    tokens = sum(1 for s in win.served for t in s.token_times
                 if win.start <= t <= win.end)
    ttft = [(s.token_times[0] if s.token_times else win.closed_at) - s.due
            for s in due_in]
    gaps = [b - a for s in win.served
            for a, b in zip(s.token_times, s.token_times[1:])
            if b <= win.end]
    failed = sum(1 for s in due_in if not s.token_times)
    return {
        "tokens_per_s": tokens / (win.end - win.start),
        "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else None,
        "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
        "attempted": len(due_in), "failed": failed,
        "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens": tokens,
        "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
        "itl_p99_ms": 1e3 * percentile(gaps, 99) if gaps else None,
    }


@dataclass
class RunData:
    """What a per-layer metric's reader gets from a traced run."""
    win: Window
    rows: list  # per launch in the traced part: (kind, [(start, n_new)])
    trace: object  # trace.Reduced of the traced part
    shapes: object  # the configuration family's shapes(cfg)
    peak: dict
    events: list  # the engine's own request events: (kind, rid, ts)
    traced: tuple  # host-clock (start, end) of the traced part
    cell: object
