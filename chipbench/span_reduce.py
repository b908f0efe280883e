"""The program's own spans and conv nodes on the device trace's clock.

``trace_reduce`` places the benchmark's host spans on the device's
clock by one point and names device ops by their HLO instruction. This
module adds what a traced window reads when the session records its own
spans (``launch/session.py``: ``submit`` > ``check_input`` >
``host_sync``, ``flush`` > ``stack``, ``run_batch`` > ``dispatch``,
``split``, ``result``) and when each kernel launch is built under
``jax.named_scope("<kind>:<node>")`` (``kernels/common.py``):

- ``Clock``: host times (``perf_counter``) to device nanoseconds by two
  points, the end of the first window mark and the end of the last,
  each as the host saw it done; ``skew_us`` is how far the two clocks
  ran apart over the window;
- ``innermost``: nested program spans flattened into the intervals of
  the innermost span open then, named by their path
  (``submit/check_input/host_sync``), for ``trace_reduce.idle_by_span``;
- ``device_by_node``: device seconds per conv node, split into the
  Pallas kernel (``tpu_custom_call`` in the compiled HLO) and every
  other op built for the node;
- ``span_metrics``: milliseconds per batch in ``check_input``, ``stack``
  and ``dispatch``, the mean queue wait and host syncs per batch; all
  None where the tracer dropped a span;
- ``glue_device_ms``: device time per batch in ops that are not a
  Pallas kernel: copies, pads, reshapes, eager checks.

All device times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from chipbench import trace_reduce

SCOPE = re.compile(r"(?:megakernel|graphkernel):([^/\"\s,}]+)")
PALLAS = 'custom_call_target="tpu_custom_call"'
OTHER = "other"


@dataclasses.dataclass(frozen=True)
class Clock:
    """Host seconds to device ns, through (host_open, dev_open) and
    (host_close, dev_close): the first and last window marks' ends."""
    host_open: float
    dev_open: float
    host_close: float
    dev_close: float

    @property
    def rate(self) -> float:
        return (self.dev_close - self.dev_open) / (self.host_close
                                                   - self.host_open)

    @property
    def skew_us(self) -> float:
        """Device time between the two points less host time, in us."""
        return ((self.dev_close - self.dev_open) / 1e3
                - (self.host_close - self.host_open) * 1e6)

    def __call__(self, t: float) -> float:
        return self.dev_open + (t - self.host_open) * self.rate


def mark_ends(ops) -> "tuple[int, int]":
    """End of the first and of the last window mark among one chip's
    ``ops`` (name, start_ns, dur_ns)."""
    shape = "f32[" + ",".join(map(str, trace_reduce.MARK)) + "]"
    ends = sorted(s + d for n, s, d in ops if shape in n)
    if len(ends) < 2:
        raise ValueError(f"no window marks in the trace (found {ends})")
    return ends[0], ends[-1]


def host_spans_on_device(host_spans, clock: Clock) -> list:
    """The benchmark's (name, start_s, end_s) as (name, start_ns,
    dur_ns) on the device's clock, for ``trace_reduce.reduce_events``."""
    return [(n, clock(a), clock(b) - clock(a)) for n, a, b in host_spans]


def innermost(spans, clock: Clock) -> list:
    """(path, start_ns, end_ns) on the device's clock: where each of the
    program's ``spans`` (``obs.Span`` of one thread, so nested) is the
    innermost one open. Intervals do not overlap."""
    by_id = {s.id: s for s in spans}
    paths: dict = {}

    def path(s):
        if s.id not in paths:
            parent = by_id.get(s.parent_id)
            paths[s.id] = (path(parent) + "/" if parent else "") + s.name
        return paths[s.id]

    out, stack = [], []
    t = None
    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            top = stack.pop()
            out.append((path(top), t, top.end_ns))
            t = top.end_ns
        if stack:
            out.append((path(stack[-1]), t, s.start_ns))
        stack.append(s)
        t = s.start_ns
    while stack:
        top = stack.pop()
        out.append((path(top), t, top.end_ns))
        t = top.end_ns
    return [(p, clock(a / 1e9), clock(b / 1e9)) for p, a, b in out if b > a]


def idle_by_program_span(device_ops, spans, clock: Clock, window) -> list:
    """Idle seconds of chip 0 in ``window`` (lo, hi ns) by the innermost
    program span open then, the largest ``trace_reduce.TOP`` first;
    ``other`` where no program span was open."""
    lo, hi = window
    ivals = [(max(s, lo), min(s + d, hi)) for _, s, d in device_ops[0]
             if s + d > lo and s < hi]
    idle = trace_reduce.idle_by_span(trace_reduce.merge(ivals),
                                     innermost(spans, clock), lo, hi)
    return [[n, v / 1e9] for n, v in sorted(
        idle.items(), key=lambda kv: -kv[1])[:trace_reduce.TOP]]


def scopes_from_hlo(hlo_text: str) -> "tuple[dict, set]":
    """From a compiled executable's HLO text: device op name
    (``trace_reduce.op_name``) -> conv node, read from the ``op_name``
    metadata that carries the launch's named scope, and the set of op
    names that are Pallas kernels (``tpu_custom_call``)."""
    scopes, kernels = {}, set()
    for line in hlo_text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if not trace_reduce._HLO.match(line):   # tuples run no op
            continue
        name = trace_reduce.op_name(line)
        m = SCOPE.search(line)
        if m:
            scopes[name] = m[1]
        if PALLAS in line:
            kernels.add(name)
    return scopes, kernels


def device_by_node(device_ops, scopes: dict, kernels: set, window) -> list:
    """[node, kernel_s, other_s] over all chips for ops that started in
    ``window``, the largest total first; ops built for no node go under
    ``other``. ``scopes`` maps an op name to its node, ``kernels`` holds
    the names of the Pallas kernels."""
    lo, hi = window
    per = defaultdict(lambda: [0.0, 0.0])
    for ops in device_ops:
        for name, s, d in ops:
            if lo <= s < hi:
                split = per[scopes.get(name, OTHER)]
                split[0 if name in kernels else 1] += min(s + d, hi) - s
    return [[n, k / 1e9, o / 1e9] for n, (k, o) in sorted(
        per.items(), key=lambda kv: -sum(kv[1]))]


def glue_device_ms(device_ops, kernels: set, window, batches: int):
    """Device ms per batch in ops that started in ``window`` and are not
    a Pallas kernel (``kernels``)."""
    if not batches:
        return None
    lo, hi = window
    ns = sum(min(s + d, hi) - s for ops in device_ops for name, s, d in ops
             if lo <= s < hi and name not in kernels)
    return ns / 1e6 / batches


def span_metrics(spans, batches: int, dropped: int) -> dict:
    """The serving layer's per-layer numbers from the program's spans of
    one window; each None where spans were dropped or nothing ran."""
    names = ("check_input_ms", "stack_ms", "dispatch_ms", "queue_wait_ms",
             "host_syncs_per_batch")
    if dropped or not batches:
        return dict.fromkeys(names)
    ms = defaultdict(float)
    count = defaultdict(int)
    wait_s, waited = 0.0, 0
    for s in spans:
        ms[s.name] += s.dur_ns / 1e6
        count[s.name] += 1
        if s.name == "flush":
            wait_s += s.attrs.get("wait_s_sum", 0.0)
            waited += s.attrs.get("n", 0)
    return {
        "check_input_ms": ms["check_input"] / batches,
        "stack_ms": ms["stack"] / batches,
        "dispatch_ms": ms["dispatch"] / batches,
        "queue_wait_ms": wait_s / waited * 1e3 if waited else None,
        "host_syncs_per_batch": count["host_sync"] / batches,
    }
