"""Per-assigned-architecture smoke tests: reduced config of the same
family, one forward + one train step on CPU, asserting shapes + no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.configs.base import SHAPES, TrainConfig, applicable_shapes
from repro.data.pipeline import lm_batch
from repro.models import encdec as ED
from repro.models import transformer as T
from repro.models.module import init_params
from repro.train.steps import init_train_state, make_train_step


ASSIGNED_DIMS = {  # exact dims from the assignment table
    "gemma3_4b": (34, 2560, 8, 4, 10240, 262144),
    "command_r_35b": (40, 8192, 64, 8, 22528, 256000),
    "mistral_large_123b": (88, 12288, 96, 8, 28672, 32768),
    "qwen3_1p7b": (28, 2048, 16, 8, 6144, 151936),
    "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000),
    "seamless_m4t_medium": (12, 1024, 16, 16, 4096, 256206),
    "qwen2_vl_72b": (80, 8192, 64, 8, 29568, 152064),
    "dbrx_132b": (40, 6144, 48, 8, 10752, 100352),
    "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 1536, 151936),
    "xlstm_125m": (12, 768, 4, 4, 0, 50304),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_dims_match_assignment(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = ASSIGNED_DIMS[arch]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert cfg.d_ff == ff and cfg.vocab_size == v
    # every layer type is defined
    assert len(cfg.layer_types) == cfg.n_layers


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = dataclasses.replace(reduced_config(arch), compute_dtype="float32")
    B, S = 2, 16
    batch = lm_batch(0, 0, B, S, cfg.vocab_size)
    if cfg.n_encoder_layers:
        params = init_params(ED.encdec_defs(cfg), jax.random.key(0))
        frames = jnp.zeros((B, 8, cfg.d_model), jnp.float32)
        logits = ED.apply_encdec(cfg, params, frames, batch["tokens"])
        batch = {**batch, "frames": frames}
    else:
        params = init_params(T.lm_defs(cfg), jax.random.key(0))
        if cfg.frontend == "vision_patches":
            batch["vision_embeds"] = jnp.zeros((B, 4, cfg.d_model))
        logits, _, _ = T.apply_lm(cfg, params, batch["tokens"],
                                  extra_embeds=batch.get("vision_embeds"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    # one train step
    state = init_train_state(cfg, params)
    step_fn = jax.jit(make_train_step(cfg, TrainConfig(learning_rate=1e-3)))
    new_state, metrics = step_fn(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(new_state["step"]) == 1
    # params actually changed
    diff = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], new_state["params"]))
    assert max(diff) > 0


@pytest.mark.parametrize("arch", ["gemma3_4b", "recurrentgemma_2b",
                                  "xlstm_125m"])
def test_long_context_archs_are_subquadratic(arch):
    assert get_config(arch).subquadratic


def test_long_500k_skips_are_documented():
    expect_skip = {"command_r_35b", "mistral_large_123b", "qwen3_1p7b",
                   "seamless_m4t_medium", "qwen2_vl_72b", "dbrx_132b",
                   "qwen3_moe_235b_a22b"}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        names = [s.name for s in applicable_shapes(cfg)]
        if arch in expect_skip:
            assert "long_500k" not in names
        else:
            assert "long_500k" in names


@pytest.mark.parametrize("arch", ["qwen3_1p7b", "gemma3_4b",
                                  "recurrentgemma_2b", "xlstm_125m"])
def test_decode_matches_full_forward(arch):
    cfg = dataclasses.replace(reduced_config(arch), compute_dtype="float32")
    params = init_params(T.lm_defs(cfg), jax.random.key(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    full_logits, _, _ = T.apply_lm(cfg, params, toks)
    cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache, _ = T.apply_lm(cfg, params, toks[:, t:t + 1],
                                  cache=cache, cache_pos=t)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    assert jnp.max(jnp.abs(dec - full_logits)) < 1e-3


def test_param_counts_in_expected_range():
    """Full configs should be in the ballpark of their nameplate sizes."""
    expected = {  # arch -> (low, high) in billions
        "gemma3_4b": (3.0, 6.0),
        "command_r_35b": (30, 40),
        "mistral_large_123b": (110, 135),
        "qwen3_1p7b": (1.2, 2.3),
        "recurrentgemma_2b": (2.0, 4.0),
        "qwen2_vl_72b": (65, 80),
        "dbrx_132b": (110, 145),
        "qwen3_moe_235b_a22b": (200, 260),
        "xlstm_125m": (0.08, 0.2),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count() / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"
