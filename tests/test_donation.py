"""Buffer-donation audit (ISSUE 8 satellite): inspect the LOWERED
StableHLO of every donation-bearing jit on the serving/training paths
and assert the input-output aliasing annotation actually survives.

Why lowered IR and not the compiled executable: XLA CPU *drops*
donation at compile time (with a warning), so a compiled-object probe
passes vacuously on CI hosts. The ``tf.aliasing_output`` arg attribute
is stamped at lowering, before the backend gets a veto — it proves the
``donate_argnums`` reached jax rather than being silently dropped by a
wrapper (the regression this audit exists for: the StreamingSession
wraps its jit in a warning filter, and a careless rewrap loses the
donation)."""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.configs.base import TrainConfig
from repro.core.decomposition import ConvLayer
from repro.core.graph import chain_graph
from repro.launch.session import StreamingSession
from repro.models import transformer as T
from repro.models.cnn import init_graph_weights
from repro.models.module import init_params
from repro.train.steps import (init_train_state, make_decode_step,
                               make_train_step)

ALIAS = "tf.aliasing_output"


def _session(**kw):
    graph = chain_graph((ConvLayer("c1", 16, 16, 3, 8, 3, pad=1, pool=2),
                         ConvLayer("c2", 8, 8, 8, 8, 3, pad=1)),
                        name="donation_probe")
    weights = init_graph_weights(graph, jax.random.key(0))
    return StreamingSession.for_graph(graph, weights, max_batch=2,
                                      sram_budget=64 * 1024, **kw)


def test_session_executable_lowers_with_input_donation():
    """The serving executable donates the input batch (argnums=(0,)).

    A CNN's output never matches its input shape, so the donation can
    never materialise as a ``tf.aliasing_output`` annotation — jax
    records the request in the lowering's ``args_info`` instead (and
    the backend decides at compile time whether the freed buffer feeds
    the temporary allocator). The auditable artifact is therefore the
    per-arg ``donated`` flag: exactly the batch arg, never the weights
    or operand tables (those serve every later call)."""
    sess = _session()
    assert sess.donate
    x = jnp.zeros((2,) + tuple(sess.graph.in_shape), jnp.float32)
    sess.run_batch(jnp.array(x))
    (ex,) = sess._executables.values()
    # the warning-filter wrapper must forward the jit's .lower — a
    # wrapper that loses the inspection surface is a wrapper nobody
    # can audit
    assert hasattr(ex, "lower")
    lowered = ex.lower(x, sess.weights, sess._ops)
    (x_info, w_info, ops_info), _kwargs = lowered.args_info
    assert x_info.donated, "donate_argnums dropped from session executable"
    assert not any(a.donated for a in jax.tree_util.tree_leaves(w_info))
    assert not any(a.donated for a in jax.tree_util.tree_leaves(ops_info))


def test_session_donate_false_lowers_without_donation():
    sess = _session(donate=False)
    x = jnp.zeros((2,) + tuple(sess.graph.in_shape), jnp.float32)
    sess.run_batch(x)
    (ex,) = sess._executables.values()
    lowered = ex.lower(x, sess.weights, sess._ops)
    assert not any(a.donated
                   for a in jax.tree_util.tree_leaves(lowered.args_info))
    assert ALIAS not in lowered.as_text()


def _lm_cfg():
    return dataclasses.replace(reduced_config("qwen3_1p7b"),
                               compute_dtype="float32")


def test_decode_step_donates_kv_cache():
    """serve.py's decode loop rebinds the cache every step; the jit
    must alias EVERY cache leaf in and out, or each step allocates a
    second full cache."""
    cfg = _lm_cfg()
    params = jax.eval_shape(
        lambda k: init_params(T.lm_defs(cfg), k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, 1, 8, dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = jax.jit(make_decode_step(cfg), donate_argnums=(1,)).lower(
        params, cache, tok, pos)
    txt = lowered.as_text()
    n_cache_leaves = len(jax.tree_util.tree_leaves(cache))
    assert txt.count(ALIAS) >= n_cache_leaves, (
        f"decode cache donation covers {txt.count(ALIAS)} buffers, "
        f"expected at least the {n_cache_leaves} cache leaves")


def test_train_step_donates_state():
    """train/loop.py rebinds the state every step; the jit must alias
    the param/moment buffers in place (what dryrun's estimator already
    assumes when it reports train memory)."""
    cfg = _lm_cfg()
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, init_params(T.lm_defs(cfg), k)),
        jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    lowered = jax.jit(make_train_step(cfg, TrainConfig()),
                      donate_argnums=(0,)).lower(state, batch)
    txt = lowered.as_text()
    n_param_leaves = len(jax.tree_util.tree_leaves(state["params"]))
    assert txt.count(ALIAS) >= n_param_leaves, (
        f"train-state donation covers {txt.count(ALIAS)} buffers, "
        f"expected at least the {n_param_leaves} param leaves")
