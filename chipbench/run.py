#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 chipbench/run.py --workload alexnet-fp32-b8 --seed 7 \
        --seconds 20 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
beside its limit, which are also the last lines of stderr.

Exits nonzero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, a device kind missing from
``chipbench/peaks.json``, or no program to run. The compilation cache
is the program's (``launch/compile_cache.py``): ``$JAX_COMPILATION_CACHE_DIR``
or ``.jax_cache/`` in this checkout.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Refused(Exception):
    """No chip to run on: the command prints no result and exits 1."""


def find_chip(chips: int) -> "tuple[dict, dict]":
    """Turn on the program's compilation cache and find ``chips`` TPU
    chips; returns the run's ``device`` entry and the chip's peaks.
    Raises ``Refused`` without a TPU, with too few chips, with a device
    kind missing from ``peaks.json``, where the Pallas kernels would run
    in interpret mode, or where the program is not in the checkout."""
    if str(ROOT / "src") not in sys.path:
        sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import work
    try:
        from repro.kernels.common import pallas_interpret_default
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    enable_compile_cache()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise Refused(f"needs a TPU, JAX found {device}")
    if device["count"] < chips:
        raise Refused(f"needs {chips} chips, found {device}")
    try:
        peak = work.peaks(device["kind"])
    except KeyError as e:
        raise Refused(str(e))
    if pallas_interpret_default():
        raise Refused("Pallas kernels would run in interpret mode")
    return device, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import bench
    spec = bench.cell_spec(args.workload)
    try:
        device, peak = find_chip(spec["cell"]["chips"])
    except Refused as e:
        print(f"chipbench: {args.workload}: {e}", file=sys.stderr,
              flush=True)
        return 1
    result = bench.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                            T_START, peak=peak, device=device)
    bench.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
