"""The traffic generator: reads a mix from ``chipbench/traffic/<name>.json``
and drives ``StreamingSession.submit`` / ``result`` with it.

``arrivals: "closed"`` is a closed loop of ``clients`` callers. Each has
one image in flight and sends its next one when its result is ready on
the device, so a slow system receives less load. Requests cycle through
the run's pool of distinct host frames, so every submit copies a frame
to the device. A request's latency runs from the start of its
``submit`` to the moment its output is ready (``block_until_ready``).

The window closes ``seconds`` after the first submit: no request is sent
after it, the ones in flight are finished, and every request sent in the
window counts towards the latencies. Images per second count the
requests finished by the close.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, List

import jax

NULL_SPAN = contextlib.nullcontext()


def no_span(name: str):
    return NULL_SPAN


@dataclasses.dataclass
class Request:
    index: int
    frame: int
    sent: float
    ticket: int
    out: object = None
    batch: int = -1


@dataclasses.dataclass
class Window:
    seconds: float          # the window's length as run
    attempted: int          # requests sent in the window
    completed: int          # of them, finished (after the drain)
    done_in_window: int     # finished by the close
    batches: int            # flushes, the drain's included
    latencies_s: List[float]
    drain_s: float          # close to the last output


def closed_loop(sess, frames, clients: int, seconds: float,
                on_done: Callable[[Request], None],
                span=no_span, clock=time.perf_counter) -> Window:
    """Run the closed loop for ``seconds``; ``on_done`` sees every
    finished request with its output. ``span(name)`` wraps each call
    into the session (``submit``, ``flush``, ``result``) and each wait
    for outputs (``wait``)."""
    inflight: deque = deque()
    unfetched: List[Request] = []
    state = {"next": 0, "batches": 0}

    def fetch():
        with span("result"):
            for r in unfetched:
                r.out = sess.result(r.ticket)
                r.batch = state["batches"]
        unfetched.clear()
        state["batches"] += 1

    def send():
        i = state["next"]
        state["next"] += 1
        frame = i % len(frames)
        t = clock()
        with span("submit"):
            ticket = sess.submit(frames[frame])
        r = Request(i, frame, t, ticket)
        inflight.append(r)
        unfetched.append(r)
        if sess.pending == 0:          # the submit flushed a batch
            fetch()

    t0 = clock()
    close = t0 + seconds
    for _ in range(clients):
        send()
    latencies, done_in_window, last = [], 0, close
    while inflight:
        head = inflight[0]
        if head.out is None:           # a part batch nobody will fill
            with span("flush"):
                sess.flush()
            fetch()
        batch = []
        while inflight and inflight[0].batch == head.batch:
            batch.append(inflight.popleft())
        with span("wait"):
            jax.block_until_ready([r.out for r in batch])
        t = clock()
        for r in batch:
            latencies.append(t - r.sent)
            done_in_window += t <= close
            on_done(r)
        if t < close:
            for _ in batch:
                send()
        last = t
    return Window(seconds=seconds, attempted=state["next"],
                  completed=len(latencies), done_in_window=done_in_window,
                  batches=state["batches"], latencies_s=latencies,
                  drain_s=max(0.0, last - close))


GENERATORS = {"closed": closed_loop}
