"""Training-loop integration: convergence, determinism across restart,
grad accumulation equivalence, watchdog, compression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import lm_batch
from repro.distributed.fault import StepWatchdog, run_with_restarts
from repro.models import transformer as T
from repro.models.module import init_params
from repro.train.loop import train_lm
from repro.train.steps import init_train_state, make_train_step


def _cfg():
    return dataclasses.replace(reduced_config("qwen3_1p7b"),
                               compute_dtype="float32")


def test_loss_decreases_on_learnable_data():
    cfg = _cfg()
    _, hist = train_lm(cfg, TrainConfig(learning_rate=3e-3), num_steps=30,
                       batch=8, seq=32)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.9


def test_crash_restart_resumes_from_checkpoint(tmp_path):
    cfg = _cfg()
    tcfg = TrainConfig(learning_rate=1e-3, checkpoint_every=5)
    calls = {"n": 0}

    def make_runner():
        def run():
            first = calls["n"] == 0
            calls["n"] += 1
            _, h = train_lm(cfg, tcfg, num_steps=12, batch=4, seq=16,
                            ckpt_dir=str(tmp_path),
                            fail_at_step=7 if first else None)
            return len(h)
        return run

    steps_after_restart = run_with_restarts(make_runner, max_restarts=2)
    # failed at step 7 after checkpointing step 5 -> resumed at 5, ran 7 more
    assert steps_after_restart == 12 - 5


def test_restart_matches_uninterrupted_run(tmp_path):
    """Determinism: crash+restore reproduces the uninterrupted loss curve."""
    cfg = _cfg()
    tcfg = TrainConfig(learning_rate=1e-3, checkpoint_every=4)
    _, clean = train_lm(cfg, tcfg, num_steps=10, batch=4, seq=16)
    try:
        train_lm(cfg, tcfg, num_steps=10, batch=4, seq=16,
                 ckpt_dir=str(tmp_path), fail_at_step=6)
    except RuntimeError:
        pass
    _, resumed = train_lm(cfg, tcfg, num_steps=10, batch=4, seq=16,
                          ckpt_dir=str(tmp_path))
    # resumed history covers steps 4..9; compare the overlap
    np.testing.assert_allclose(
        [h["loss"] for h in resumed],
        [h["loss"] for h in clean[4:]], rtol=1e-4)


def test_grad_accumulation_matches_single_batch():
    cfg = _cfg()
    params = init_params(T.lm_defs(cfg), jax.random.key(0))
    batch = lm_batch(0, 0, 8, 16, cfg.vocab_size)
    s1 = init_train_state(cfg, params)
    s2 = jax.tree.map(jnp.copy, s1)
    one = make_train_step(cfg, TrainConfig(learning_rate=1e-3, accum_steps=1))
    acc = make_train_step(cfg, TrainConfig(learning_rate=1e-3, accum_steps=4))
    n1, m1 = jax.jit(one)(s1, batch)
    n2, m2 = jax.jit(acc)(s2, batch)
    # same global batch, same mean gradient -> same update (fp32 tolerance)
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        n1["params"], n2["params"]))
    assert max(diffs) < 1e-4   # fp32 summation-order tolerance
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4


def test_int8_grad_compression_still_converges():
    cfg = _cfg()
    tcfg = TrainConfig(learning_rate=3e-3, accum_steps=2,
                       grad_compression="int8")
    _, hist = train_lm(cfg, tcfg, num_steps=20, batch=8, seq=32)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(ratio=3.0, warmup=3)
    for _ in range(10):
        wd.observe(0.1)
    assert wd.observe(1.0) is True
    assert wd.stragglers == 1
    assert wd.observe(0.1) is False


def test_run_with_restarts_gives_up_after_max():
    def make_runner():
        def run():
            raise RuntimeError("always fails")
        return run

    with pytest.raises(RuntimeError):
        run_with_restarts(make_runner, max_restarts=2)
