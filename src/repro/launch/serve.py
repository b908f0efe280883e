"""Serving entry point: batched LM decode, or streaming CNN image serving.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --batch 4 --prompt-len 16 --gen-len 32

  PYTHONPATH=src python -m repro.launch.serve --cnn \
      --batch 8 --requests 32

  PYTHONPATH=src python -m repro.launch.serve --cnn \
      --precision int8 --batch 8 --requests 32   # quantized megakernel

  PYTHONPATH=src python -m repro.launch.serve --cnn --network resnet18 \
      --mode megakernel --batch 4 --requests 8   # residual graph serving

  PYTHONPATH=src python -m repro.launch.serve --cnn --network vgg16 \
      --batch 4 --requests 8

  PYTHONPATH=src python -m repro.launch.serve --cnn --mode auto \
      --autotune-cache tune.json --batch 16 --requests 32   # measured plan
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.models import transformer as T
from repro.models.module import init_params
from repro.train.steps import make_decode_step, make_prefill_step


def make_cnn_session(graph, weights, *, mode: str = "wave",
                     precision: str = "fp32", max_batch: int = 8,
                     sram_kb: int = 128, pool_backend: str = "xla",
                     qnet=None, fallback=None, guard=None,
                     autotune_cache=None, tracer=None,
                     compile_retries: int = 2):
    """The serving session ``--cnn`` builds: plan the graph at
    ``sram_kb``, and for ``precision="int8"`` calibrate it on two random
    frames (unless a calibrated ``qnet`` is given) and serve the
    quantized megakernel. Returns ``(session, mode)`` — the mode int8
    actually serves."""
    from repro.launch.session import StreamingSession

    if precision == "int8":
        if mode not in ("megakernel", "graphkernel", "auto"):
            print("--precision int8 runs the quantized megakernel; "
                  f"overriding --mode {mode}")
            mode = "megakernel"
        if qnet is None:
            from repro.quant import calibrate_graph
            calib = jax.random.normal(jax.random.key(7),
                                      (2,) + graph.in_shape)
            qnet = calibrate_graph(graph, weights, calib)
    sess = StreamingSession.for_graph(graph, weights,
                                      sram_budget=sram_kb * 1024,
                                      max_batch=max_batch,
                                      mode=mode,
                                      pool_backend=pool_backend,
                                      precision=precision,
                                      qnet=qnet,
                                      fallback=fallback,
                                      guard=guard,
                                      autotune_cache=autotune_cache,
                                      tracer=tracer,
                                      compile_retries=compile_retries)
    return sess, mode


def serve_images(sess, imgs) -> dict:
    """Serve ``imgs`` (N, H, W, C) as single-image requests: one padded
    warm-up flush compiles the session's (only) executable, then every
    image is submitted and the queue drained in ``max_batch`` flushes.
    Returns the per-request outputs and the host-clock timings."""
    t0 = time.perf_counter()
    jax.block_until_ready(sess.result(sess.submit(imgs[0])))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tickets = [sess.submit(imgs[i]) for i in range(imgs.shape[0])]
    sess.flush()
    outs = [sess.result(t) for t in tickets]
    jax.block_until_ready(outs)
    serve_s = time.perf_counter() - t0
    return {"outs": outs, "compile_s": compile_s, "serve_s": serve_s,
            "img_per_s": imgs.shape[0] / serve_s}


def cnn_main(args):
    """Serve single-image requests through a compiled StreamingSession:
    the chosen network's graph (``--network alexnet | vgg16 | resnet18
    | facedet | mobilenet_v1 | mobilenet_v2``, core/model_zoo.py) is
    lowered to tile schedules once, then every ``--batch`` submits
    share one cached executable (paper §7). ResNet-18 serves with its
    residual adds fused into the megakernel epilogues and its
    projection shortcuts streamed as 1x1 convs; the MobileNets stream
    their depthwise layers through the natural per-group kernel path.
    ``--precision int8`` calibrates the graph on a few random batches
    and serves the quantized megakernel path (fixed-point datapath,
    paper Table 2)."""
    from repro.core.model_zoo import network_graph
    from repro.models.cnn import init_graph_weights
    from repro.obs import Tracer, render_metrics, write_chrome_trace

    tracer = Tracer() if args.trace_out else None
    graph = network_graph(args.network)
    weights = init_graph_weights(graph, jax.random.key(0))
    sess, _ = make_cnn_session(
        graph, weights, mode=args.mode, precision=args.precision,
        max_batch=args.batch, sram_kb=args.sram_kb,
        pool_backend=args.pool_backend, fallback=args.fallback or None,
        guard=args.guard or None, autotune_cache=args.autotune_cache,
        tracer=tracer)
    if sess.tuned is not None:
        print(f"autotuned plan ({sess.tuned.us_per_batch:.0f} us/batch): "
              + ", ".join(f"{n}={m}" for n, m in sess.tuned.node_modes))
    imgs = jax.random.normal(jax.random.key(99),
                             (args.requests,) + graph.in_shape)
    run = serve_images(sess, imgs)
    print(f"compile+first flush: {run['compile_s']:.2f} s")
    print(f"served {args.requests} requests in {run['serve_s']*1e3:.0f} ms "
          f"({run['img_per_s']:.1f} img/s), "
          f"compiles={sess.compile_count}, batched calls={sess.calls}")
    print(sess.describe())
    if tracer is not None:
        n = write_chrome_trace(args.trace_out, tracer)
        dispatches = sum(s.name == "dispatch" for s in tracer.spans("run"))
        print(f"trace: {n} events -> {args.trace_out} "
              f"(trace-time execute spans={tracer.span_count('execute')}, "
              f"dispatch spans={dispatches}); open in "
              f"chrome://tracing or ui.perfetto.dev")
    if args.metrics:
        print(render_metrics())
    if args.health:
        import json
        print(json.dumps(sess.health(), indent=2))


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--cnn", action="store_true",
                    help="serve CNN image requests via StreamingSession")
    ap.add_argument("--network", default="alexnet",
                    choices=("alexnet", "vgg16", "resnet18", "facedet",
                             "mobilenet_v1", "mobilenet_v2",
                             "convnext_t"),
                    help="which NetworkGraph to serve (--cnn): the "
                         "AlexNet chain, the VGG-16 stack, ResNet-18 "
                         "with residual adds + projection shortcuts, "
                         "the compact face-detection trunk (tiny frames, "
                         "the batch-throughput serving shape), or the "
                         "MobileNet-v1/v2 depthwise-separable stacks "
                         "(the grouped per-group kernel path), or the "
                         "ConvNeXt-T trunk (norm and GELU epilogues; "
                         "--mode megakernel only)")
    ap.add_argument("--requests", type=int, default=32,
                    help="number of single-image requests (--cnn)")
    ap.add_argument("--sram-kb", type=int, default=128,
                    help="planner buffer budget in KiB (--cnn)")
    ap.add_argument("--mode", choices=("wave", "scan", "megakernel",
                                       "graphkernel", "auto"),
                    default="wave",
                    help="streaming executor: wave-parallel fused "
                         "dispatches (default), serial scan replay, "
                         "one persistent Pallas megakernel per layer "
                         "(partial sums stay in VMEM; bias+ReLU+pool "
                         "fused in the kernel epilogue), the "
                         "whole-graph kernel (fused layer chains share "
                         "one pallas_call and a VMEM activation arena), "
                         "or 'auto' — the measured autotuner times "
                         "candidate plans per conv node at startup and "
                         "serves the winning mixed-mode plan")
    ap.add_argument("--autotune-cache", default=None,
                    help="JSON path for --mode auto measurement reuse: "
                         "loaded before tuning (a hit skips the search), "
                         "saved with the winner after")
    ap.add_argument("--pool-backend", choices=("xla", "fused"),
                    default="xla",
                    help="CONV+POOL layers: XLA maxpool after the "
                         "executor, or the fused Pallas conv+pool kernel "
                         "(ignored by --mode megakernel, which fuses "
                         "pooling itself)")
    ap.add_argument("--fallback", action="store_true",
                    help="resolve the graph through the graceful-"
                         "degradation runtime (repro.runtime): a node "
                         "that fails to plan/lower/launch at the chosen "
                         "mode degrades to the next cheaper executor "
                         "(graphkernel -> megakernel -> wave -> scan) "
                         "instead of failing the whole session")
    ap.add_argument("--guard", action="store_true",
                    help="post-execution numeric guards: quarantine a "
                         "batch whose output goes NaN/Inf (fp32) or "
                         "saturates wholesale (int8) and re-run it on "
                         "the reference path (implies --fallback)")
    ap.add_argument("--health", action="store_true",
                    help="after serving, print the session's health "
                         "report as JSON: per-node executor modes, "
                         "degradation events, shed/deadline/guard/"
                         "retry counters")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace_events JSON of "
                         "the session (plan/lower/compile spans, kernel "
                         "launch spans, submit/flush/run_batch/result "
                         "spans) to this path (--cnn)")
    ap.add_argument("--metrics", action="store_true",
                    help="after serving, print the metrics registry as "
                         "plain text: kernel launches, cache hit/miss, "
                         "queue depth, queue-wait histogram, host syncs "
                         "(--cnn)")
    ap.add_argument("--precision", choices=("fp32", "int8"),
                    default="fp32",
                    help="int8 calibrates the stack (PTQ, a few random "
                         "batches) and serves the quantized megakernel: "
                         "int8 operands, int32 VMEM accumulators, "
                         "requantize fused into each kernel epilogue "
                         "(implies --mode megakernel)")
    args = ap.parse_args()
    if args.cnn:
        return cnn_main(args)

    cfg = dataclasses.replace(C.reduced_config(args.arch),
                              compute_dtype="float32")
    params = init_params(T.lm_defs(cfg), jax.random.key(0))
    B, P, G = args.batch, args.prompt_len, args.gen_len
    S_max = P + G

    # donate the KV cache (arg 1): each step rebinds it, so XLA updates
    # the buffers in place instead of doubling peak memory — same
    # aliasing the dryrun decode estimator models (donation audit:
    # tests/test_donation.py). CPU drops donation with a warning per
    # executable; suppress just that message
    import warnings
    _decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    def decode(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return _decode(*args)
    prompts = jax.random.randint(jax.random.key(1), (B, P), 0,
                                 cfg.vocab_size)

    # prefill via repeated decode into a full-size cache (simple + exact)
    cache = T.init_cache(cfg, B, S_max, dtype=jnp.float32)
    t0 = time.perf_counter()
    logits = None
    for t in range(P):
        logits, cache = decode(params, cache, prompts[:, t:t + 1],
                               jnp.asarray(t))
    print(f"prefill {B}x{P}: {(time.perf_counter()-t0)*1e3:.0f} ms")

    tok = jnp.argmax(logits, -1)[:, None]
    toks = [tok]
    t0 = time.perf_counter()
    for t in range(G - 1):
        logits, cache = decode(params, cache, tok, jnp.asarray(P + t))
        tok = jnp.argmax(logits, -1)[:, None]
        toks.append(tok)
    dt = time.perf_counter() - t0
    gen = jnp.concatenate(toks, axis=1)
    print(f"decode {B}x{G}: {dt*1e3:.0f} ms ({B*G/dt:.0f} tok/s)")
    print("ids[0]:", gen[0].tolist())


if __name__ == "__main__":
    main()
