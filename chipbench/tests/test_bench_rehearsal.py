"""The harness end to end on the CPU: a tiny chain graph served through
the cell's session in Pallas interpret mode, driven by the benchmark's
own traffic generator and metric readers.

Besides the sound run, each fault the cells can have is planted in the
timed path and must turn ``correct`` false; and the control (the
reference at three bfloat16 passes) must fail the configuration's limit
where the program passes it.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import bench

ROOT = bench.ROOT
CELL = "alexnet-fp32-b8"
SEED = 2**31 + 17          # seeds run past 32 signed bits
WINDOW_S = 0.3

TINY = {
    "name": "tiny", "reference": "chain_ref.py", "network": "facedet",
    "network_args": {"in_hw": 16, "width": 4, "depth": 4},
    "in_shape": [16, 16, 3],
    "layers": [
        {"name": "c1", "out_c": 4, "kernel": 3, "stride": 2, "pad": 1,
         "pool": 2, "pool_stride": 2},
        {"name": "c2", "out_c": 8, "kernel": 1},
        {"name": "c3", "out_c": 8, "kernel": 3, "pad": 1, "pool": 2,
         "pool_stride": 2},
        {"name": "c4", "out_c": 16, "kernel": 1}],
}
FAKE_PEAK = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11}


def tiny_spec():
    """The cell's spec, its network swapped for the tiny chain; the
    session settings, limits, traffic and metrics stay the cell's."""
    spec = bench.cell_spec(CELL)
    real = spec["config"]
    spec["config"] = dict(TINY, session=real["session"],
                          limits=real["limits"])
    return spec


def run(trace=False, control=False):
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    return bench.run_cell(tiny_spec(), SEED, WINDOW_S, trace,
                          time.perf_counter(), peak=FAKE_PEAK,
                          device=device, control=control)


@pytest.fixture(scope="module")
def sound():
    return run(control=True)


def test_sound_run_is_correct_with_every_key(sound):
    assert list(sound)[:5] == ["correct", "attempted", "failed", "metrics",
                               "device"]
    assert list(sound)[-1] == "checks"
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert set(sound["metrics"]) == {m["name"] for m in
                                     bench.cell_spec(CELL)["end_to_end"]}
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    assert sound["device"]["platform"] == "cpu"
    c = sound["checks"]["max_rel_err"]
    assert c["value"] <= c["limit"]
    # the window compiled nothing, the session compiled once, and the
    # warm-up answers plus a sample of the window's were compared
    assert sound["info"]["window_compiles"] == 0
    assert sound["info"]["session_compiles"] == 1
    assert sound["info"]["answers_compared"] > 8


def test_control_fails_the_limit_the_program_passes(sound):
    limit = sound["checks"]["max_rel_err"]["limit"]
    assert sound["checks"]["max_rel_err"]["value"] < limit
    control = sound["info"]["control"]
    assert control["correct"] is False
    assert control["checks"]["max_rel_err"]["value"] > limit


def test_traced_run_reports_per_layer_metrics():
    r = run(trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: the trace-read metrics stay silent
    # and the rest are there
    assert set(r["metrics"]) == {"session_host_ms", "plan_s", "compile_s",
                                 "mfu"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert "breakdown" not in r


def _alter_value(y):
    return y.at[:, 0, 0, 0].add(1e-3 * (1.0 + jax.numpy.abs(y).max()))


def _swap_answers(y):
    return y[::-1]


@pytest.mark.parametrize("fault", [_alter_value, _swap_answers],
                         ids=["answer_altered", "answers_swapped"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    from repro.launch.session import StreamingSession
    real = StreamingSession.run_batch

    def broken(self, x):
        return fault(real(self, x))

    monkeypatch.setattr(StreamingSession, "run_batch", broken)
    r = run()
    assert r["correct"] is False
    c = r["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_sampler_is_seeded_and_uniform():
    class R:
        def __init__(self, i):
            self.frame, self.out = i, i

    picks = []
    for _ in range(2):
        s = bench.Sampler(8, np.random.default_rng([SEED, 1]))
        for i in range(1000):
            s(R(i))
        picks.append(sorted(f for f, _ in s.kept))
    assert picks[0] == picks[1] and len(picks[0]) == 8
    assert max(picks[0]) > 100        # not just the first requests


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_are_found_by_name():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (bench.HERE / "configs" / cfg["reference"]).is_file()
        assert "max_rel_err" in cfg["limits"]
    for w in b["workloads"]:
        spec = bench.cell_spec(w["name"])
        assert spec["traffic"]["arrivals"] in bench.loadgen.GENERATORS
    for m in b["end_to_end"] + b["per_layer"]:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
