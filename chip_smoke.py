#!/usr/bin/env python3
"""Chip smoke test: full-width AlexNet served through StreamingSession.

    python chip_smoke.py

Builds the AlexNet conv stack at its published widths (227x227x3 input,
96/256/384/384/256 channels, groups=2, 3/2 pools) with seeded random
weights, and serves 16 single-image requests at ``max_batch=8`` through
the session ``python -m repro.launch.serve --cnn`` builds — a padded
warm-up flush, then two full flushes — once per phase:

  reference    ``models/cnn.py::apply_graph`` at Precision.HIGHEST
  wave         fp32, the serving default
  megakernel   fp32, one Pallas kernel per layer
  graphkernel  fp32, fused chains of layers in one Pallas kernel
  megakernel   int8 after ``calibrate_graph``, bit-exact against the
               int32 reference (``kernels/wave_replay_q/ref.py``)

Each phase prints one JSON line: mode, precision, batch, compile count,
kernel launches by family, degradation events, every node's executor,
the error against the reference next to the stated tolerance, and —
informational only, not benchmark metrics — compile seconds and served
images per second. The last line is ``{"ok": true, "device": {...}}``
only when every phase passed on a TPU; off the chip, or when any phase
fails, the script exits nonzero and the cause goes to stderr.

One process drives one chip. The persistent compilation cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in
this checkout.
"""
import json
import sys
import time
import traceback
from pathlib import Path

N_REQUESTS = 16
MAX_BATCH = 8
SRAM_KB = 128                # the planner's buffer budget (``--sram-kb``)
# fp32 phases: max |y - reference| over max |reference| — the kernels
# and the reference both run fp32 (HIGHEST) matmuls, so only summation
# order differs; a single bf16 pass would miss this by an order
FP32_TOL = 1e-4


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


def _launches(snapshot: dict) -> dict:
    counters = snapshot.get("counters", {})
    return {name.split(".", 1)[1]: int(v) for name, v in counters.items()
            if name.startswith("kernel_launches.") and v}


def _executed_modes(graph, tracer, precision) -> dict:
    """The executor each conv node ran, read off the trace-time
    ``execute`` spans of the session's one compile: a wave or megakernel
    span names its node, a graphkernel span its chain's head."""
    from repro.core.streaming import (compile_graph, graph_chain_programs,
                                      plan_graph)
    members = {}
    if any(s.attrs.get("kind") == "graphkernel"
           for s in tracer.spans("execute")):
        progs = compile_graph(graph, plan_graph(graph, SRAM_KB * 1024))
        chains, _, _ = graph_chain_programs(
            graph, progs, quantized=precision == "int8", batch=MAX_BATCH)
        members = {c.convs[0]: c.convs for c in chains}
    ran = {}
    for s in tracer.spans("execute"):
        node, kind = s.attrs["node"], s.attrs["kind"]
        for n in (members[node] if kind == "graphkernel" else (node,)):
            ran.setdefault(n, []).append(kind)
    return {n.name: "+".join(ran.get(n.name, ["none"]))
            for n in graph.conv_nodes()}


def _serve_phase(name, graph, weights, imgs, *, mode, precision, qnet=None):
    """Serve ``imgs`` through a fresh session; the phase record."""
    from repro.launch.serve import make_cnn_session, serve_images
    from repro.obs import Tracer
    from repro.obs.metrics import MetricsRegistry, use_registry

    tracer = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        sess, mode = make_cnn_session(
            graph, weights, mode=mode, precision=precision,
            max_batch=MAX_BATCH, sram_kb=SRAM_KB, qnet=qnet,
            compile_retries=0, tracer=tracer)
        run = serve_images(sess, imgs)
        health = sess.health()
        launches = _launches(reg.snapshot())
    import jax.numpy as jnp
    y = jnp.stack(run["outs"])
    modes = _executed_modes(graph, tracer, precision)
    rec = {
        "phase": name, "mode": mode, "precision": precision,
        "batch": MAX_BATCH, "requests": int(imgs.shape[0]),
        "compile_count": sess.compile_count,
        "launches": launches,
        "degradation_events": len(health["degradation_events"]),
        "node_modes": modes,
        "informational": {"compile_s": run["compile_s"],
                          "img_per_s": run["img_per_s"]},
    }
    problems = []
    if sess.compile_count != 1:
        problems.append(f"compile_count {sess.compile_count} != 1")
    if rec["degradation_events"]:
        problems.append(f"{rec['degradation_events']} degradation events")
    wrong = {n: m for n, m in modes.items() if m != mode}
    if wrong:
        problems.append(f"nodes that did not run {mode} once: {wrong}")
    return y, rec, problems


def _errors(y, ref) -> dict:
    import jax.numpy as jnp
    abs_err = float(jnp.max(jnp.abs(y - ref)))
    return {"max_abs_err": abs_err,
            "max_rel_err": abs_err / float(jnp.max(jnp.abs(ref)))}


def reference(graph, weights, imgs):
    """``apply_graph`` at Precision.HIGHEST (on TPU the default fp32
    precision is one bf16 pass); None when it is not finite."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import apply_graph

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x: apply_graph(graph, weights, x))(imgs)
    ref = jax.block_until_ready(ref)
    ok = bool(jnp.isfinite(ref).all())
    print(json.dumps({"phase": "reference", "precision": "fp32-highest",
                      "batch": int(imgs.shape[0]), "shape": list(ref.shape),
                      "finite": ok, "ok": ok, "informational": {
                          "compile_s": time.perf_counter() - t0}}),
          flush=True)
    return ref if ok else None


def run_phases(graph, weights, imgs, ref) -> list:
    """Serve ``imgs`` in every kernel phase; the names of the phases
    that failed (each phase's JSON line is printed as it ends)."""
    import jax
    import jax.numpy as jnp

    from repro.core.quantization import dequantize_int8
    from repro.quant import calibrate_graph
    from repro.quant.accuracy import quant_graph_reference_acts

    failed = []
    phases = [("wave", "wave", "fp32"), ("megakernel", "megakernel", "fp32"),
              ("graphkernel", "graphkernel", "fp32"),
              ("megakernel-int8", "megakernel", "int8")]
    for name, mode, precision in phases:
        try:
            qnet = None
            if precision == "int8":
                calib = jax.random.normal(jax.random.key(7),
                                          (2,) + graph.in_shape)
                qnet = calibrate_graph(graph, weights, calib)
            y, rec, problems = _serve_phase(name, graph, weights, imgs,
                                            mode=mode, precision=precision,
                                            qnet=qnet)
            if y.shape != ref.shape or not bool(jnp.isfinite(y).all()):
                problems.append(f"output {y.shape} not finite / not "
                                f"{ref.shape}")
            err = _errors(y, ref)
            if precision == "fp32":
                rec.update(err, fp32_tol=FP32_TOL)
                if not err["max_rel_err"] <= FP32_TOL:
                    problems.append(f"max_rel_err {err['max_rel_err']:.3g} "
                                    f"> {FP32_TOL}")
            else:
                out = graph.output
                ref_q = quant_graph_reference_acts(qnet, imgs)[out]
                want = dequantize_int8(ref_q, qnet.scales[out])
                rec["bit_exact_vs_int32_ref"] = bool(jnp.array_equal(y, want))
                rec["informational"].update(
                    {f"vs_fp32_{k}": v for k, v in err.items()})
                if not rec["bit_exact_vs_int32_ref"]:
                    n_diff = int(jnp.sum(y != want))
                    problems.append(f"{n_diff} values differ from the "
                                    f"int32 reference")
        except Exception as e:        # report every phase, then fail
            traceback.print_exc()
            rec = {"phase": name, "mode": mode, "precision": precision,
                   "error": f"{type(e).__name__}: {str(e)[:500]}"}
            problems = [rec["error"]]
        rec["ok"] = not problems
        print(json.dumps(rec), flush=True)
        if problems:
            failed.append(name)
            print(f"chip_smoke: {name}: " + "; ".join(problems),
                  file=sys.stderr)
    return failed


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return _fail(f"run from the repository root: {e}")
    enable_compile_cache()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        return _fail(f"needs a TPU, JAX found {device}")
    from repro.kernels.common import pallas_interpret_default
    if pallas_interpret_default():
        return _fail("Pallas kernels would run in interpret mode")

    from repro.core.model_zoo import network_graph
    from repro.models.cnn import init_graph_weights

    graph = network_graph("alexnet")
    weights = init_graph_weights(graph, jax.random.key(0))
    imgs = jax.random.normal(jax.random.key(99),
                             (N_REQUESTS,) + graph.in_shape)
    ref = reference(graph, weights, imgs)
    failed = (["reference"] if ref is None
              else run_phases(graph, weights, imgs, ref))

    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
