"""Span tracer: nested, thread-safe spans with monotonic timestamps.

The paper argues entirely in measured quantities — per-layer cycle
counts, DRAM accesses per decomposition choice (Tables 1/2) — so the
repro needs a first-class way to *see* where a forward pass spends its
life. A ``Tracer`` records nested spans around the resolver stages
(plan -> lower -> compile), per-node / per-fused-chain kernel
launches (trace time, same semantics as the launch counters), autotune
candidate probes, and the serving calls (submit, flush, run_batch,
result, and the input checks and device waits inside them). Spans carry
monotonic ``perf_counter_ns`` timestamps and structured attributes and
export losslessly to Chrome/Perfetto ``trace_events`` JSON
(obs/export.py).

Instrumentation is **opt-in with a no-op fast path**: sites call the
module-level ``span(...)`` / ``event(...)`` helpers, which read one
module global and return a shared ``nullcontext`` (or do nothing) when
no tracer is active — the disabled path is a single load+compare, so
leaving the hooks compiled in costs nothing measurable (gated <= 2%
on the AlexNet megakernel smoke bench, a CPU interpret-mode timing).
Activate with
``set_tracer(t)`` or scoped via ``use_tracer(t)``; ``StreamingSession
(tracer=...)`` and ``serve.py --trace-out`` do this for you.

Thread safety: each thread keeps its own open-span stack (so nesting
is correct under concurrent serving) while the finished-span list is
shared under a lock. A span that exits via an exception still closes —
with an ``error`` attribute — so traces of failing runs are complete.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple


class Span:
    """One closed-or-open interval: name, category, [start, end) ns.

    Made by ``Tracer.span`` and entered with ``with``: entering gives it
    its id, parent, thread and start; leaving gives it its end."""

    __slots__ = ("id", "parent_id", "name", "cat", "start_ns", "end_ns",
                 "tid", "attrs", "_tracer", "_open_stack")

    def __init__(self, id: int, parent_id: Optional[int], name: str,
                 cat: str, start_ns: int, tid: int,
                 attrs: Dict[str, object],
                 tracer: Optional["Tracer"] = None):
        self.id = id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None   # set at close
        self.tid = tid
        self.attrs = attrs
        self._tracer = tracer
        self._open_stack: Optional[list] = None

    def __enter__(self) -> "Span":
        return self._tracer._open(self)

    def __exit__(self, kind, e, tb) -> bool:
        if e is not None:
            self.attrs["error"] = f"{kind.__name__}: {e}"
        self.end_ns = time.perf_counter_ns()
        stack, self._open_stack, self._tracer = self._open_stack, None, None
        stack.pop()     # a closed span holds no tracer: no cycle to collect
        return False

    @property
    def dur_ns(self) -> Optional[int]:
        return None if self.end_ns is None else self.end_ns - self.start_ns

    def as_dict(self) -> dict:
        return {"id": self.id, "parent_id": self.parent_id,
                "name": self.name, "cat": self.cat,
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "tid": self.tid, "attrs": dict(self.attrs)}

    def __repr__(self) -> str:
        dur = self.dur_ns
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"dur={'open' if dur is None else f'{dur / 1e3:.1f}us'})")


class Tracer:
    """Collects spans and instant events; bounded so long-lived servers
    cannot grow a trace without limit (``max_spans``, oldest kept —
    the drop count is reported so truncation is never silent)."""

    def __init__(self, max_spans: int = 200_000):
        self.max_spans = int(max_spans)
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._events: List[dict] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0

    # -- recording -----------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, cat: str = "", **attrs) -> Span:
        """A nested span to enter with ``with``; it yields the ``Span``
        so callers can attach attributes mid-flight. Exceptions close
        the span with an ``error`` attribute and propagate. A class
        context, not a generator: it costs a few microseconds a span."""
        return Span(0, None, name, cat, 0, 0, attrs, self)

    def _open(self, sp: Span) -> Span:
        stack = self._stack()
        sp.parent_id = stack[-1].id if stack else None
        sp.id = next(self._ids)
        sp.tid = threading.get_ident()
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
            else:
                self._spans.append(sp)
        stack.append(sp)
        sp._open_stack = stack
        sp.start_ns = time.perf_counter_ns()
        return sp

    def event(self, name: str, cat: str = "", **attrs) -> None:
        """Record one instant event (no duration)."""
        stack = self._stack()
        ev = {"name": name, "cat": cat,
              "ts_ns": time.perf_counter_ns(),
              "tid": threading.get_ident(),
              "parent_id": stack[-1].id if stack else None,
              "attrs": dict(attrs)}
        with self._lock:
            if len(self._events) >= self.max_spans:
                self.dropped += 1
            else:
                self._events.append(ev)

    # -- reading -------------------------------------------------------
    def spans(self, cat: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if cat is not None:
            out = [s for s in out if s.cat == cat]
        return out

    def events(self, cat: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = list(self._events)
        if cat is not None:
            out = [e for e in out if e["cat"] == cat]
        return out

    def span_count(self, cat: Optional[str] = None) -> int:
        return len(self.spans(cat))

    def mark(self) -> int:
        """Current span-list index — pair with ``spans_since``."""
        with self._lock:
            return len(self._spans)

    def spans_since(self, mark: int,
                    cats: Optional[Iterable[str]] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans[mark:])
        if cats is not None:
            cats = tuple(cats)
            out = [s for s in out if s.cat in cats]
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._events.clear()
            self.dropped = 0


# ---------------------------------------------------------------------------
# Module-level current tracer: the one global the disabled fast path reads
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None

# one shared, stateless, reentrant no-op context manager: the disabled
# span() path allocates nothing
_NULL_CM = contextlib.nullcontext()


def current_tracer() -> Optional[Tracer]:
    return _ACTIVE


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the process-wide active tracer (or disable
    with ``None``). Returns the previous tracer."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    return prev


@contextlib.contextmanager
def use_tracer(tracer: Optional[Tracer]):
    """Scoped activation. ``None`` leaves the current tracer in place
    (so a session without its own tracer never masks an outer one)."""
    if tracer is None:
        yield current_tracer()
        return
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


def span(name: str, cat: str = "", **attrs):
    """Open a span on the active tracer — or a shared no-op context
    when tracing is disabled (one global read, zero allocation)."""
    t = _ACTIVE
    if t is None:
        return _NULL_CM
    return t.span(name, cat, **attrs)


def event(name: str, cat: str = "", **attrs) -> None:
    """Record an instant event on the active tracer, if any."""
    t = _ACTIVE
    if t is not None:
        t.event(name, cat, **attrs)
