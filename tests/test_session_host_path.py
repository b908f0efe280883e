"""The micro-batch queue's host path: numpy frames are checked on the
host, stacked and padded in numpy and put on the device once per batch;
``flush`` does not re-check the batch it built; one jitted unstack per
output shape hands each request its row."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import chain_graph
from repro.launch.session import StreamingSession
from repro.models.cnn import init_graph_weights
from repro.obs import MetricsRegistry, Tracer, use_registry

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _session(layers=None, max_batch=4, **kw):
    layers = layers or (ConvLayer("h1", 8, 8, 3, 4, 3, stride=1, pad=1),
                        ConvLayer("h2", 8, 8, 4, 4, 3, stride=1, pad=1))
    g = chain_graph(layers, name="tiny_host")
    return StreamingSession.for_graph(
        g, init_graph_weights(g, jax.random.key(1)), sram_budget=64 * 1024,
        max_batch=max_batch, mode="scan", **kw)


def _frames(sess, n, seed=2):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n,) + sess.graph.in_shape, dtype=np.float32)


def _serve(sess, frames):
    """Submit ``frames`` as single requests, flush the rest, return the
    outputs as host arrays in ticket order."""
    tickets = [sess.submit(f) for f in frames]
    sess.flush()
    return [np.asarray(sess.result(t)) for t in tickets]


def _batches_seen(sess):
    """Record every batch the run path receives, as a host copy."""
    seen = []
    run = sess.run_batch

    def spy(x):
        seen.append(np.asarray(x).copy())
        return run(x)

    sess.run_batch = spy
    return seen


@pytest.mark.parametrize("n", [4, 3], ids=["full", "partial"])
def test_host_and_device_frames_give_identical_outputs(n):
    """The same frames, sent as numpy or as ``jax.Array``: the run path
    receives the same float32 batch bit for bit, and every request gets
    the same output."""
    sess = _session(max_batch=4, donate=False)
    seen = _batches_seen(sess)
    frames = _frames(sess, n)
    host = _serve(sess, list(frames))
    dev = _serve(sess, [jnp.asarray(f) for f in frames])
    assert len(seen) == 2
    assert seen[0].dtype == seen[1].dtype == np.float32
    assert np.array_equal(seen[0], seen[1])
    assert np.array_equal(seen[0][n:], np.zeros_like(seen[0][n:]))
    for h, d in zip(host, dev):
        assert h.dtype == d.dtype and np.array_equal(h, d)


def test_a_mixed_batch_is_stacked_on_the_device():
    """One host frame beside a device frame: the batch is built on the
    device, no host batch is counted, and the outputs still match."""
    with use_registry(MetricsRegistry()) as reg:
        sess = _session(max_batch=2)
        frames = _frames(sess, 2)
        mixed = _serve(sess, [frames[0], jnp.asarray(frames[1])])
        assert reg.counter("session.host_batches").value == 0
        host = _serve(sess, list(frames))
        assert reg.counter("session.host_batches").value == 1
    for a, b in zip(mixed, host):
        assert np.array_equal(a, b)


def _bad(kind, sess):
    f = _frames(sess, 1)[0]
    if kind == "nan":
        f[1, 2, 0] = np.nan
    elif kind == "inf":
        f[0, 0, 2] = -np.inf
    elif kind == "shape":
        f = f[:, :-1]
    elif kind == "dtype":
        f = f.astype(np.int32)
    return f


@pytest.mark.parametrize("kind,says", [("nan", "NaN/Inf"),
                                       ("inf", "NaN/Inf"),
                                       ("shape", "got shape"),
                                       ("dtype", "got dtype int32")])
def test_bad_host_frame_is_refused_at_submit_like_a_device_frame(kind, says):
    sess = _session(max_batch=2)
    bad = _bad(kind, sess)
    with pytest.raises(ValueError) as host_err:
        sess.submit(bad)
    with pytest.raises(ValueError) as dev_err:
        sess.submit(jnp.asarray(bad))
    assert says in str(host_err.value)
    assert str(host_err.value) == str(dev_err.value)
    assert sess.pending == 0


def test_host_frames_open_no_host_sync():
    """Host frames are checked without waiting on the device: no
    ``host_sync`` span, ``session.host_syncs`` stays 0, and each flush
    counts one host-built batch."""
    t = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        sess = _session(max_batch=2, tracer=t)
        _serve(sess, list(_frames(sess, 5)))      # 2 full + 1 partial
        assert reg.counter("session.host_syncs").value == 0
        assert reg.counter("session.host_batches").value == 3
    names = [s.name for s in t.spans()]
    assert "host_sync" not in names
    assert names.count("flush") == names.count("stack") == 3
    assert names.count("split") == names.count("run_batch") == 3
    assert names.count("check_input") == 5      # one per request only


def test_unstack_is_built_once_per_output_shape():
    """A session's first flush lowers the forward and the unstack of its
    output shape; later flushes, partial ones too, lower nothing, and a
    second session of the same output shape lowers only its forward."""
    layers = (ConvLayer("u1", 6, 6, 3, 5, 3, stride=1, pad=1),)
    lowerings = [0]

    def count(event, duration, **kw):
        lowerings[0] += event == LOWERING

    def lowered(fn):
        before = lowerings[0]
        fn()
        return lowerings[0] - before

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        sess = _session(layers, max_batch=3)
        frames = list(_frames(sess, 3))
        assert lowered(lambda: _serve(sess, frames)) == 2
        assert lowered(lambda: _serve(sess, frames[:1])) == 0
        assert lowered(lambda: _serve(sess, frames)) == 0
        other = _session(layers, max_batch=3)
        assert lowered(lambda: _serve(other, frames[:2])) == 1
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert sess.compile_count == other.compile_count == 1


@pytest.mark.parametrize("kind,syncs", [("numpy", 0), ("jax", 1)])
def test_public_run_batch_still_refuses_nan(kind, syncs):
    """``run_batch`` checks its caller's batch: a host batch on the
    host, a device batch with one ``host_sync``."""
    with use_registry(MetricsRegistry()) as reg:
        sess = _session(max_batch=2)
        x = _frames(sess, 2)
        x[1, 3, 3, 1] = np.nan
        if kind == "jax":
            x = jnp.asarray(x)
        with pytest.raises(ValueError, match="run_batch: input contains "
                                             "NaN/Inf"):
            sess.run_batch(x)
        assert reg.counter("session.host_syncs").value == syncs
    assert sess.calls == 0
