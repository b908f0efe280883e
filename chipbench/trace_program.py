#!/usr/bin/env python3
"""Traced windows of one cell with the program's own spans, on the chip.

    python3 chipbench/trace_program.py --workload alexnet-fp32-b8 \
        --seed 3100000001 --seconds 51 --windows on,off,on,off,on,off

Sets the cell up as ``bench.run_cell`` does, then runs one profiled
window per entry of ``--windows`` in the same session: ``on`` installs
a fresh ``Tracer`` on the session for the window, ``off`` leaves it
None. Each window prints one JSON line: requests sent, images per
second, batches, and for the first ``on`` window what ``span_reduce`` reads:
the serving spans' metrics, ``clock_skew_us``, ``idle_gaps`` (the
benchmark's host spans), ``idle_by_program_span``, ``device_by_node``,
and ``glue_device_ms``. A device op's conv node is read from the
``op_name`` metadata of the session's compiled HLO (the TPU trace's
events carry no such stat), and whether it is a Pallas kernel from its
``custom_call_target``. Only the first ``on`` window is read in full:
the windows after it give their request counts alone, for timing what
the program tracer costs (reading a b1 window takes minutes).
``--out`` also writes every line to a file. The benchmark's own runs
never run this; it exits 1 without a TPU, as ``run.py`` does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from chipbench import (bench, loadgen, plain, run, span_reduce,  # noqa: E402
                       trace_reduce)

SPANS_PER_REQUEST = 10
MAX_RATE = 2000          # requests/s no cell comes near (PERF.md §6)


def hlo_scopes(sess, max_batch: int, in_shape) -> "tuple[dict, set]":
    """Op name -> conv node, and the Pallas kernels' op names, from the
    session's compiled executable."""
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct((max_batch,) + tuple(in_shape), jnp.float32)
    fn = sess._executable(sess._exec_key(x.shape, x.dtype))
    text = fn.lower(x, sess.weights, sess._ops).compile().as_text()
    return span_reduce.scopes_from_hlo(text)


def reduce_window(trace_dir, host_spans, t_open, t_close, tracer, batches,
                  scopes, kernels) -> dict:
    """What an ``on`` window reads: the serving spans' metrics, and
    where the trace has a device plane, the device's side of them.
    ``scopes`` maps device op names to conv nodes; ``kernels`` holds
    the Pallas kernels' op names."""
    spans = tracer.spans()
    out = dict(span_reduce.span_metrics(spans, batches, tracer.dropped),
               spans=len(spans), dropped=tracer.dropped)
    ops = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
    if not ops:
        return out
    first, last = span_reduce.mark_ends(ops[0])
    clock = span_reduce.Clock(t_open, first, t_close, last)
    window = trace_reduce.find_window(ops[0])
    base = trace_reduce.reduce_events(
        ops, span_reduce.host_spans_on_device(host_spans, clock), window)
    out.update(
        glue_device_ms=span_reduce.glue_device_ms(ops, kernels, window,
                                                  batches),
        clock_skew_us=clock.skew_us, busy_s=base["busy_s"],
        window_s=base["window_s"], n_ops=base["n_ops"],
        idle_gaps=base["idle_gaps"],
        idle_by_program_span=span_reduce.idle_by_program_span(
            ops, spans, clock, window),
        device_by_node=span_reduce.device_by_node(ops, scopes, kernels,
                                                  window))
    return out


def run_windows(spec: dict, seed: int, seconds: float, modes, device: dict,
                t_start: float, emit=print) -> list:
    """Set the cell up as ``bench.run_cell`` does, then run one profiled
    window per entry of ``modes`` (``on``: a fresh Tracer on the session
    for the window; ``off``: none). The first ``on`` window is read in
    full, the rest give their request counts. ``emit`` sees each
    line."""
    import jax
    import numpy as np

    from repro.launch.serve import make_cnn_session
    from repro.obs import Tracer

    cfg, traffic = spec["config"], spec["traffic"]
    ref = bench.load_module(bench.HERE / "configs" / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    graph = bench.build_graph(cfg)
    bench.check_graph(graph, nodes)
    params = jax.jit(lambda k: plain.init_params(nodes, k))(bench._key(seed))
    frames = np.random.default_rng(seed).standard_normal(
        (traffic["frames"],) + tuple(cfg["in_shape"]), dtype=np.float32)
    max_batch = traffic["max_batch"]
    sess_cfg = cfg["session"]
    sess, _ = make_cnn_session(
        graph, params, mode=sess_cfg["mode"],
        precision=sess_cfg["precision"], max_batch=max_batch,
        sram_kb=sess_cfg["sram_kb"])
    warm = [sess.result(sess.submit(frames[0]))]
    if max_batch > 1:
        tickets = [sess.submit(frames[i % len(frames)])
                   for i in range(1, max_batch + 1)]
        warm += [sess.result(t) for t in tickets]
    jax.block_until_ready(warm)
    mark = bench.window_mark()
    gen = loadgen.GENERATORS[traffic["arrivals"]]
    scopes, kernels = hlo_scopes(sess, max_batch, cfg["in_shape"])
    reduced = False

    lines = [{"workload": spec["cell"]["name"], "seed": seed,
              "setup_s": time.perf_counter() - t_start, "device": device}]
    emit(lines[0])
    for i, mode in enumerate(modes):
        tracer = Tracer(max_spans=int(SPANS_PER_REQUEST * MAX_RATE
                                      * (seconds + 10))) \
            if mode == "on" else None
        spans = bench.HostSpans()
        trace_dir = tempfile.mkdtemp(prefix="chipbench_program_")
        try:
            jax.profiler.start_trace(
                trace_dir, profiler_options=bench.profile_options())
            try:
                mark()
                t_open = time.perf_counter()
                sess.tracer = tracer
                try:
                    window = gen(sess, frames, traffic["clients"], seconds,
                                 lambda r: None, span=spans)
                finally:
                    sess.tracer = None
                mark()
                t_close = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            line = {"window": i, "program_tracer": mode,
                    "attempted": window.attempted,
                    "img_per_s": window.done_in_window / seconds,
                    "batches": window.batches,
                    "session_host_ms": sum(
                        spans.totals[n] for n in ("submit", "flush",
                                                  "result"))
                    / max(window.batches, 1) * 1e3}
            if tracer is not None and not reduced:
                reduced = True
                line.update(reduce_window(trace_dir, spans.spans, t_open,
                                          t_close, tracer, window.batches,
                                          scopes, kernels))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        lines.append(line)
        emit(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--windows", default="on,off")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    modes = args.windows.split(",")
    if set(modes) - {"on", "off"}:
        ap.error(f"--windows takes on/off, got {args.windows!r}")

    spec = bench.cell_spec(args.workload)
    try:
        device, _ = run.find_chip(spec["cell"]["chips"])
    except run.Refused as e:
        print(f"trace_program: {args.workload}: {e}", file=sys.stderr)
        return 1
    lines = run_windows(spec, args.seed, args.seconds, modes, device, T0,
                        emit=lambda x: print(json.dumps(x), flush=True))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("".join(json.dumps(x) + "\n"
                                          for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
