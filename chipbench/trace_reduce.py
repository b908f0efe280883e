"""From a profiler trace to device busy time, idle share, op counts and
the breakdown a traced run prints.

A trace reduces to three inputs: the device operations of each chip
(name, start, duration), the benchmark's own host spans (name, start,
duration), and the traced window. The profiler records the device
alone: its host tracer, even at its lowest level, slows these
host-bound loops two to four times. So the window is marked on the
device, by a small op of a shape no network has (``MARK``), run before
the first request and after the last output; the TPU trace names both
runs alike, so the first and the last bound the window. The host spans,
timed by the benchmark on the host's clock, are placed on the device's
clock by the first mark: the host sees it done when it ends.
All times are nanoseconds on the profiler's clock.

- busy: the union of the intervals in which an operation ran on a chip,
  clipped to the window, averaged over the chips;
- ops: the operations that started inside the window, over all chips;
- idle gaps: the window minus busy, split by the host span that was
  open at the time (``other`` where none was);
- device ops: total device seconds per operation name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
MARK = (3, 5, 7, 11)
TOP = 10


_HLO = re.compile(r"%?(\S+) = (\w+\[[^\]]*\])\S* ([\w-]+)\(")


def op_name(name: str) -> str:
    """A device op's name without its operands: the TPU trace names an op
    by its whole HLO instruction."""
    m = _HLO.match(name)
    return f"{m[1]} {m[3]} {m[2]}" if m else name[:120]


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def idle_by_span(busy, spans, lo, hi) -> dict:
    """Idle nanoseconds in [lo, hi) per host span name; ``busy`` is a
    merged interval list, ``spans`` (name, start, end) do not overlap."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out = defaultdict(float)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][1] < b:
            name, s, e = spans[i]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov
                covered += ov
            i += 1
        if b - a - covered > 0:
            out["other"] += b - a - covered
    return dict(out)


def reduce_events(device_ops, host_spans, window) -> dict:
    """``device_ops``: one list per chip of (name, start_ns, dur_ns);
    ``host_spans``: (name, start_ns, dur_ns); ``window``: (lo, hi) ns.

    Returns busy_s, window_s, n_ops and the breakdown lists."""
    lo, hi = window
    busy_ns, n_ops = [], 0
    per_name = defaultdict(float)
    merged0 = []
    for chip, ops in enumerate(device_ops):
        ivals = [(s, s + d) for _, s, d in ops]
        merged = merge(_clip(ivals, lo, hi))
        if chip == 0:
            merged0 = merged
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, d in ops:
            if lo <= s < hi:
                n_ops += 1
                per_name[name] += min(s + d, hi) - s
    spans = [(n, s, s + d) for n, s, d in host_spans]
    idle = idle_by_span(merged0, spans, lo, hi)
    return {
        "busy_s": sum(busy_ns) / max(len(busy_ns), 1) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "n_ops": n_ops,
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            per_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[f"idle during {n}", v / 1e9] for n, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def find_window(ops) -> "tuple[int, int]":
    """(lo, hi): from the end of the first mark to the start of the last,
    among one chip's ``ops`` (name, start_ns, dur_ns)."""
    shape = "f32[" + ",".join(map(str, MARK)) + "]"
    marks = sorted((s, s + d) for n, s, d in ops if shape in n)
    if len(marks) < 2 or marks[0][1] >= marks[-1][0]:
        raise ValueError(f"no window marks in the trace (found {marks})")
    return marks[0][1], marks[-1][0]


def read_xplane(path: str) -> list:
    """The device operations of each chip in one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    device_ops = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if DEVICE_OP_LINE not in lines:
                raise ValueError(f"{plane.name} has no {DEVICE_OP_LINE!r} "
                                 f"line (has {sorted(lines)})")
            device_ops.append([(op_name(ev.name), ev.start_ns,
                                ev.duration_ns)
                               for ev in lines[DEVICE_OP_LINE].events])
    return device_ops


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, "
                         f"found {found}")
    return found[0]


def reduce_trace(trace_dir: str, host_spans=(), t_open: float = 0.0):
    """Reduce the one trace under ``trace_dir``; None where the trace
    holds no device plane (a CPU run has nothing to read).
    ``host_spans`` are (name, start_s, end_s) on the host's clock, and
    ``t_open`` is the host time at which the first mark was seen done."""
    ops = read_xplane(find_xplane(trace_dir))
    if not ops:
        return None
    lo, hi = find_window(ops[0])
    spans = [(n, lo + (a - t_open) * 1e9, (b - a) * 1e9)
             for n, a, b in host_spans]
    return reduce_events(ops, spans, (lo, hi))
