"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own that this module finds by name:

- ``chipbench/configs/<config>.json``: the network as it is run, the
  session's settings, and the limit of the comparison; its ``reference``
  names the plain reference module beside it;
- ``chipbench/traffic/<traffic>.json``: the mix, read by the generator
  that ``arrivals`` names in ``loadgen.GENERATORS``;
- ``chipbench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number, or None where the run holds nothing to read.

A run sets up (weights and frames from the seed, the session built as
``serve.py --cnn`` builds it, every shape of the window warmed up),
measures for ``seconds``, reads the device's peak memory, frees the
session, and only then runs the reference over a seeded sample of the
answers served in the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from chipbench import loadgen, plain, trace_reduce, work

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
HOST_SPANS = ("submit", "flush", "result", "wait")
_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = json.loads(Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path} "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config": json.loads((ROOT / entry["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


class HostSpans:
    """The benchmark's own spans around its calls into the session, on
    the host's clock: each (name, start, end), and seconds per name."""

    def __init__(self):
        self.spans: list = []
        self.totals = {n: 0.0 for n in HOST_SPANS}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        yield
        end = time.perf_counter()
        self.spans.append((name, t, end))
        self.totals[name] += end - t


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    seconds: float
    setup_s: float
    window: loadgen.Window
    nodes: list                   # conv nodes, as the reference has them
    max_batch: int
    precision: str
    peak: dict
    program_spans: list           # the session Tracer's set-up spans
    host_spans: Optional[dict]    # traced runs only
    trace: Optional[dict]         # traced runs on a chip only


class Sampler:
    """A uniform sample of ``k`` finished requests, drawn from the seed
    (reservoir sampling), kept with their outputs."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.kept: list = []

    def __call__(self, req) -> None:
        if len(self.kept) < self.k:
            self.kept.append((req.frame, req.out))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = (req.frame, req.out)
        self.seen += 1


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def check_graph(graph, nodes) -> None:
    """The program's conv nodes have the configuration's shapes."""
    keys = ("in_h", "in_w", "in_c", "out_c", "kernel", "stride", "pad",
            "groups", "pool", "pool_stride")
    have = sorted((n.name,) + tuple(getattr(n.layer, k) for k in keys[:-1])
                  + ((n.layer.pool_stride or n.layer.pool),)
                  for n in graph.conv_nodes())
    want = sorted((n["name"],) + tuple(n[k] for k in keys) for n in nodes)
    if have != want:
        raise ValueError(f"{graph.name}: the program's conv nodes differ "
                         f"from the configuration's:\n{have}\n{want}")


def build_graph(cfg: dict):
    from repro.core.model_zoo import network_graph
    return network_graph(cfg["network"], **cfg.get("network_args", {}))


def reference_outputs(ref, cfg, params, frames, idx, batch, conv_fn):
    """The reference's output for each frame in ``idx``, batch by batch."""
    import jax
    fwd = jax.jit(lambda p, x: ref.forward(cfg, p, x, conv_fn))
    out = {}
    for i in range(0, len(idx), batch):
        part = idx[i:i + batch]
        ys = fwd(params, jax.numpy.asarray(frames[np.asarray(part)]))
        for j, f in enumerate(part):
            out[f] = ys[j]
    return out


def max_rel_err(answers, refs) -> float:
    """Widest gap over the answers: max |y - ref| / max |ref| per answer."""
    import jax.numpy as jnp
    worst = 0.0
    for frame, y in answers:
        r = refs[frame]
        if y.shape != r.shape:
            return float("inf")
        err = float(jnp.max(jnp.abs(y - r)) / jnp.max(jnp.abs(r)))
        if not np.isfinite(err):
            return float("inf")
        worst = max(worst, err)
    return worst


def judge(answers, refs, limit: float, failed: int) -> "tuple[bool, dict]":
    """``correct`` and each number compared beside its limit."""
    err = max_rel_err(answers, refs)
    checks = {"max_rel_err": {"value": err, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    return err <= limit and failed == 0, checks


def profile_options():
    """The profiler on the device alone: its Python and host tracers
    slow these host-bound loops two to four times."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def window_mark():
    """The op that marks the traced window on the device (see
    ``trace_reduce``): a function that runs it to the end."""
    import jax
    import jax.numpy as jnp
    op = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.zeros(trace_reduce.MARK, jnp.float32)

    def mark():
        op(x).block_until_ready()

    mark()                             # compiled in set-up
    return mark


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, *, peak: dict, device: dict,
             control: bool = False) -> dict:
    """One run; returns the result line's object. ``control`` also
    judges the control (the reference at three bfloat16 passes, put in
    the program's place) on the same frames, for setting the limit; the
    benchmark's runs never do."""
    import jax

    from repro.launch.serve import make_cnn_session
    from repro.obs import Tracer

    cfg, traffic = spec["config"], spec["traffic"]
    ref = load_module(HERE / "configs" / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    graph = build_graph(cfg)
    check_graph(graph, nodes)
    key = _key(seed)
    params = jax.jit(lambda k: plain.init_params(nodes, k))(key)
    rng = np.random.default_rng(seed)
    frames = rng.standard_normal(
        (traffic["frames"],) + tuple(cfg["in_shape"]), dtype=np.float32)
    max_batch = traffic["max_batch"]
    sess_cfg = cfg["session"]
    tracer = Tracer()
    sess, _ = make_cnn_session(
        graph, params, mode=sess_cfg["mode"],
        precision=sess_cfg["precision"], max_batch=max_batch,
        sram_kb=sess_cfg["sram_kb"], tracer=tracer)
    # warm up every shape the window uses: a padded flush of one
    # request, then a full batch (its stack and per-row slices)
    warm = [(0, sess.result(sess.submit(frames[0])))]
    if max_batch > 1:
        tickets = [(i % len(frames), sess.submit(frames[i % len(frames)]))
                   for i in range(1, max_batch + 1)]
        warm += [(f, sess.result(t)) for f, t in tickets]
    jax.block_until_ready([y for _, y in warm])
    sess.tracer = None
    mark = window_mark() if trace else None
    setup_s = time.perf_counter() - t_start

    sampler = Sampler(traffic["sample"], np.random.default_rng([seed, 1]))
    spans = HostSpans() if trace else None
    window_compiles = [0]

    def count(event, duration, **kw):
        window_compiles[0] += event == _MLIR_EVENT

    gen = loadgen.GENERATORS[traffic["arrivals"]]
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace \
        else None
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
        try:
            if trace:
                mark()
                t_open = time.perf_counter()
            window = gen(sess, frames, traffic["clients"], seconds,
                         sampler, span=spans or loadgen.no_span)
            if trace:
                mark()
        finally:
            if trace:
                jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device,
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    compile_count = sess.compile_count
    del sess

    try:
        reduced = (trace_reduce.reduce_trace(trace_dir, spans.spans, t_open)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    answers = warm + sampler.kept
    idx = sorted({f for f, _ in answers})
    refs = reference_outputs(ref, cfg, params, frames, idx, max_batch,
                             plain.conv)
    limit = cfg["limits"]["max_rel_err"]
    failed = window.attempted - window.completed
    correct, checks = judge(answers, refs, limit, failed)
    info = {"answers_compared": len(answers), "window_compiles":
            window_compiles[0], "session_compiles": compile_count,
            "batches": window.batches, "drain_s": window.drain_s}
    if control:
        crefs = reference_outputs(ref, cfg, params, frames, idx, max_batch,
                                  plain.conv_bf16x3)
        c_correct, c_checks = judge([(f, crefs[f]) for f, _ in answers],
                                    refs, limit, 0)
        info["control"] = {"correct": c_correct, "checks": c_checks}

    run = Run(seconds=seconds, setup_s=setup_s, window=window, nodes=nodes,
              max_batch=max_batch, precision=sess_cfg["precision"],
              peak=peak, program_spans=tracer.spans(),
              host_spans=spans.totals if spans else None, trace=reduced)
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
    result = {"correct": bool(correct), "attempted": window.attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["info"] = info
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
