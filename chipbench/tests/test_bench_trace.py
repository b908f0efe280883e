"""Trace reduction (``chipbench/trace_reduce.py``) on traces built here."""
import pytest

from chipbench import trace_reduce as tr

MS = 1_000_000  # ns


def test_busy_is_the_union_of_overlapping_ops():
    # window 0..100 ms; ops 10-30, 20-40 (overlap), 50-60, and one op
    # that straddles the close (95-110) and counts up to it
    ops = [("a", 10 * MS, 20 * MS), ("b", 20 * MS, 20 * MS),
           ("a", 50 * MS, 10 * MS), ("c", 95 * MS, 15 * MS)]
    r = tr.reduce_events([ops], [], (0, 100 * MS))
    assert r["busy_s"] == pytest.approx((30 + 10 + 5) / 1e3)
    assert r["window_s"] == pytest.approx(0.1)
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(0.55)
    assert r["n_ops"] == 4
    # per-name device seconds, the largest first
    assert r["device_ops"][0] == ["a", pytest.approx(0.030)]


def test_ops_outside_the_window_are_not_counted():
    ops = [("early", -20 * MS, 10 * MS), ("in", 10 * MS, 10 * MS),
           ("late", 120 * MS, 5 * MS)]
    r = tr.reduce_events([ops], [], (0, 100 * MS))
    assert r["n_ops"] == 1
    assert r["busy_s"] == pytest.approx(0.010)


def test_busy_is_averaged_over_chips():
    chip0 = [("x", 0, 40 * MS)]
    chip1 = [("x", 0, 20 * MS)]
    r = tr.reduce_events([chip0, chip1], [], (0, 100 * MS))
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["n_ops"] == 2


def test_idle_gaps_are_labelled_by_the_host_span_open_then():
    # device busy 0-10 and 60-70; host: submit 10-30, wait 30-60,
    # nothing 70-80, result 80-100
    ops = [("k", 0, 10 * MS), ("k", 60 * MS, 10 * MS)]
    spans = [("submit", 10 * MS, 20 * MS), ("wait", 30 * MS, 30 * MS),
             ("result", 80 * MS, 20 * MS)]
    r = tr.reduce_events([ops], spans, (0, 100 * MS))
    gaps = dict((n, v) for n, v in r["idle_gaps"])
    assert gaps == {"idle during wait": pytest.approx(0.030),
                    "idle during submit": pytest.approx(0.020),
                    "idle during result": pytest.approx(0.020),
                    "idle during other": pytest.approx(0.010)}
    # the labelled idle time is all of the idle time
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert [n for n, _ in r["idle_gaps"]][0] == "idle during wait"


def test_breakdown_lists_at_most_ten_entries():
    ops = [(f"op{i}", i * MS, MS // 2) for i in range(30)]
    r = tr.reduce_events([ops], [], (0, 100 * MS))
    assert len(r["device_ops"]) == tr.TOP
    assert r["n_ops"] == 30


def test_merge():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 6]]


def _mark(start, dur):
    name = f"fusion.1 fusion f32[{','.join(map(str, tr.MARK))}]"
    return (name, start, dur)


def test_host_spans_and_window_read_from_a_recorded_trace(tmp_path,
                                                         monkeypatch):
    import jax
    import jax.numpy as jnp

    from chipbench import bench

    # a trace recorded on the CPU, with the benchmark's options and
    # marks: it holds no device plane, so there is nothing to read
    mark = bench.window_mark()
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=bench.profile_options())
    try:
        mark()
        f(x).block_until_ready()
        mark()
    finally:
        jax.profiler.stop_trace()
    assert tr.read_xplane(tr.find_xplane(str(tmp_path))) == []
    assert tr.reduce_trace(str(tmp_path)) is None

    # on a device plane: the window runs from the first mark's end to the
    # last mark's start, and host spans land on the device's clock by
    # the first mark (seen done on the host at t_open = 5.0 s)
    ops = [("k", 40 * MS, 10 * MS), _mark(102 * MS, MS),
           _mark(0, 2 * MS), ("k", 10 * MS, 10 * MS)]
    assert tr.find_window(ops) == (2 * MS, 102 * MS)
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "read_xplane", lambda path: [ops])
    spans = [("submit", 5.020, 5.040), ("wait", 5.040, 5.080)]
    r = tr.reduce_trace("trace", spans, t_open=5.0)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["n_ops"] == 2                  # the marks are not counted
    gaps = dict(r["idle_gaps"])
    # submit 22-42 ms: idle 22-40; wait 42-82 ms: idle 50-82
    assert gaps["idle during submit"] == pytest.approx(0.018)
    assert gaps["idle during wait"] == pytest.approx(0.032)


def test_find_window_refuses_a_trace_without_marks():
    with pytest.raises(ValueError, match="no window marks"):
        tr.find_window([("k", 0, MS), _mark(5 * MS, MS)])
    with pytest.raises(ValueError, match="no window marks"):
        tr.find_window([_mark(0, 5 * MS), _mark(2 * MS, MS)])


def test_op_names_drop_the_operands():
    hlo = ("%traced.6 = f32[8,13,14,256]{3,2,1,0:T(8,128)S(1)} custom-call("
           "s32[1,2,8]{2,1,0:T(2,128)S(1)} %copy-done.3), "
           "custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(hlo) == "traced.6 custom-call f32[8,13,14,256]"
    assert tr.op_name("copy-start.2") == "copy-start.2"
