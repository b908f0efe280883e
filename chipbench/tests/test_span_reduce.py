"""The program's spans and conv nodes on the device trace's clock
(``chipbench/span_reduce.py``), and ``trace_program.py`` on the CPU."""
import dataclasses
import time

import jax
import pytest

from chipbench import span_reduce as sr
from chipbench import trace_program, trace_reduce

MS = 1_000_000  # ns


@dataclasses.dataclass
class S:
    """A finished span as ``obs.Span`` has it."""
    id: int
    parent_id: object
    name: str
    start_ns: int
    end_ns: int
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_ns(self):
        return self.end_ns - self.start_ns


# host seconds == device seconds, from 0
SAME = sr.Clock(0.0, 0, 1.0, 1e9)


def test_two_points_place_host_times_on_the_device_clock():
    # the first mark ends at 2 ms on the device, seen done at 5.0 s on
    # the host; the last ends 100.01 ms later, seen 100 ms later
    c = sr.Clock(5.0, 2 * MS, 5.1, 102.01 * MS)
    assert c(5.0) == pytest.approx(2 * MS)
    assert c(5.1) == pytest.approx(102.01 * MS)
    assert c(5.05) == pytest.approx(52.005 * MS)
    assert c.skew_us == pytest.approx(10.0)
    # the benchmark's own spans land by the same two points
    (name, start, dur), = sr.host_spans_on_device([("submit", 5.02, 5.04)],
                                                  c)
    assert name == "submit"
    assert start == pytest.approx(c(5.02)) and dur == pytest.approx(
        20.002 * MS)
    # one point alone (trace_reduce) is the special case of no skew
    assert sr.Clock(5.0, 2 * MS, 6.0, 1002 * MS).skew_us == pytest.approx(0)


def test_mark_ends_are_the_first_and_last_marks():
    mark = f"fusion.1 fusion f32[{','.join(map(str, trace_reduce.MARK))}]"
    ops = [("k", 40 * MS, MS), (mark, 100 * MS, 3 * MS), (mark, 0, 2 * MS)]
    assert sr.mark_ends(ops) == (2 * MS, 103 * MS)
    with pytest.raises(ValueError, match="no window marks"):
        sr.mark_ends([("k", 0, MS), (mark, 5 * MS, MS)])


def _tree():
    """submit 0-100 > check_input 10-40 > host_sync 20-35;
    flush 50-90 > stack 55-60, run_batch 60-85 > dispatch 70-80;
    result 120-130 (ms)."""
    return [S(1, None, "submit", 0, 100 * MS),
            S(2, 1, "check_input", 10 * MS, 40 * MS),
            S(3, 2, "host_sync", 20 * MS, 35 * MS),
            S(4, 1, "flush", 50 * MS, 90 * MS, {"wait_s_sum": 0.3, "n": 3}),
            S(5, 4, "stack", 55 * MS, 60 * MS),
            S(6, 4, "run_batch", 60 * MS, 85 * MS),
            S(7, 6, "dispatch", 70 * MS, 80 * MS),
            S(8, None, "result", 120 * MS, 130 * MS)]


def test_nested_spans_flatten_to_the_innermost_by_path():
    got = [(p, a / MS, b / MS) for p, a, b in sr.innermost(_tree(), SAME)]
    assert got == [
        ("submit", 0, 10), ("submit/check_input", 10, 20),
        ("submit/check_input/host_sync", 20, 35),
        ("submit/check_input", 35, 40), ("submit", 40, 50),
        ("submit/flush", 50, 55), ("submit/flush/stack", 55, 60),
        ("submit/flush/run_batch", 60, 70),
        ("submit/flush/run_batch/dispatch", 70, 80),
        ("submit/flush/run_batch", 80, 85), ("submit/flush", 85, 90),
        ("submit", 90, 100), ("result", 120, 130)]
    # the flattened intervals cover exactly what the top spans cover
    assert sum(b - a for _, a, b in got) == 100 + 10


def test_idle_time_goes_to_the_innermost_program_span():
    # device busy 0-22 and 75-125 ms of a 0-150 ms window
    ops = [[("k", 0, 22 * MS), ("k", 75 * MS, 50 * MS)]]
    idle = dict(sr.idle_by_program_span(ops, _tree(), SAME, (0, 150 * MS)))
    want = {"submit/check_input/host_sync": 13,   # 22-35
            "submit/check_input": 5,               # 35-40
            "submit": 10,                          # 40-50
            "submit/flush": 5,                     # 50-55
            "submit/flush/stack": 5,               # 55-60
            "submit/flush/run_batch": 10,          # 60-70
            "submit/flush/run_batch/dispatch": 5,  # 70-75
            "result": 5,                           # 125-130
            "other": 20}                           # 130-150
    assert idle == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    # all of the idle time is there, the largest first
    assert sum(idle.values()) == pytest.approx((150 - 72) / 1e3)
    names = [n for n, _ in sr.idle_by_program_span(ops, _tree(), SAME,
                                                   (0, 150 * MS))]
    assert names[0] == "other" and len(names) <= trace_reduce.TOP


def test_device_time_by_conv_node_splits_kernel_from_glue():
    ops = [("traced.6 custom-call f32[8,13,13,384]", 0, 5 * MS),
           ("fusion.2 fusion f32[8,15,15,384]", 5 * MS, 2 * MS),
           ("traced.7 custom-call f32[8,27,27,96]", 7 * MS, 3 * MS),
           ("copy.1 copy f32[1,227,227,3]", 10 * MS, MS),
           ("custom-call.3 custom-call f32[5,5,48,256]", 12 * MS, MS),
           ("traced.6 custom-call f32[8,13,13,384]", 20 * MS, 5 * MS),
           ("traced.7 custom-call f32[8,27,27,96]", 200 * MS, 3 * MS)]
    scopes = {"traced.6 custom-call f32[8,13,13,384]": "conv3",
              "fusion.2 fusion f32[8,15,15,384]": "conv3",
              "traced.7 custom-call f32[8,27,27,96]": "conv1"}
    kernels = {"traced.6 custom-call f32[8,13,13,384]",
               "traced.7 custom-call f32[8,27,27,96]"}
    got = sr.device_by_node([ops], scopes, kernels, (0, 100 * MS))
    # a custom-call that is no Pallas kernel is glue, here under no node
    assert got == [["conv3", pytest.approx(0.010), pytest.approx(0.002)],
                   ["conv1", pytest.approx(0.003), 0.0],
                   ["other", 0.0, pytest.approx(0.002)]]
    # glue: the pad fusion, the copy and the other custom-call, per
    # batch of two
    assert sr.glue_device_ms([ops], kernels, (0, 100 * MS), 2) \
        == pytest.approx(2.0)
    assert sr.glue_device_ms([ops], kernels, (0, 100 * MS), 0) is None


def test_span_metrics_per_batch_and_silent_on_drops():
    spans = _tree()
    m = sr.span_metrics(spans, batches=2, dropped=0)
    assert m == {"check_input_ms": pytest.approx(15.0),
                 "stack_ms": pytest.approx(2.5),
                 "dispatch_ms": pytest.approx(5.0),
                 "queue_wait_ms": pytest.approx(100.0),
                 "host_syncs_per_batch": 0.5}
    assert set(sr.span_metrics(spans, 2, dropped=1).values()) == {None}
    assert set(sr.span_metrics(spans, 0, dropped=0).values()) == {None}


def _named_session():
    from repro.core.decomposition import ConvLayer
    from repro.core.graph import chain_graph
    from repro.launch.session import StreamingSession
    from repro.models.cnn import init_graph_weights
    layers = (ConvLayer("conv1", 8, 8, 3, 4, 3, stride=1, pad=1),
              ConvLayer("conv2", 8, 8, 4, 4, 3, stride=1, pad=1))
    g = chain_graph(layers, name="tiny_scopes")
    return StreamingSession.for_graph(
        g, init_graph_weights(g, jax.random.key(1)), sram_budget=64 * 1024,
        max_batch=2, mode="megakernel"), g


def test_conv_nodes_read_from_the_compiled_hlo():
    sess, g = _named_session()
    scopes, kernels = trace_program.hlo_scopes(sess, 2, g.in_shape)
    assert set(scopes.values()) == {"conv1", "conv2"}
    assert kernels == set()         # interpret mode lowers no kernel
    # keys are op names as the trace reduction writes them: name, kind
    # and shape
    assert all(len(k.split()) == 3 for k in scopes)


def test_scopes_from_hlo_lines():
    hlo = "\n".join([
        "ENTRY %main.4 (Arg_0.1: f32[8]) -> f32[8] {",
        '  %traced.6 = f32[8,13,14,256]{3,2,1,0} custom-call(f32[8] %p), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(traced)/megakernel:conv2/pallas_call" source_file="x.py"}',
        '  ROOT %fusion.3 = f32[8,228,228,3]{3,2,1,0} fusion(f32[8] %q), '
        'kind=kLoop, metadata={op_name="jit(traced)/graphkernel:s1b1_c1/'
        'pad"}',
        '  %copy.1 = f32[8]{0} copy(f32[8] %r), metadata={op_name="jit('
        'traced)/isfinite"}'])
    assert sr.scopes_from_hlo(hlo) == (
        {"traced.6 custom-call f32[8,13,14,256]": "conv2",
         "fusion.3 fusion f32[8,228,228,3]": "s1b1_c1"},
        {"traced.6 custom-call f32[8,13,14,256]"})


@pytest.mark.parametrize("cell,syncs", [("alexnet-fp32-b8", 9.0),
                                        ("resnet18-fp32-b1-stream", 2.0)])
def test_windows_on_the_cpu_read_the_serving_spans(cell, syncs):
    """The tool end to end on a tiny chain in interpret mode: an ``on``
    window reads max_batch + 1 host syncs per batch and drops nothing;
    the CPU's trace has no device plane, so the device keys are absent;
    an ``off`` window records no span."""
    from chipbench import bench
    from chipbench.tests.test_bench_rehearsal import TINY
    spec = bench.cell_spec(cell)
    spec["config"] = dict(TINY, session=spec["config"]["session"],
                          limits=spec["config"]["limits"])
    lines = trace_program.run_windows(spec, 2**31 + 5, 0.3, ["on", "off"],
                                      {"platform": "cpu"},
                                      time.perf_counter(), emit=lambda x: 0)
    head, on, off = lines
    assert head["workload"] == cell and head["setup_s"] > 0
    assert on["program_tracer"] == "on" and on["attempted"] > 0
    assert on["host_syncs_per_batch"] == syncs
    assert on["dropped"] == 0 and on["spans"] > 0
    for k in ("check_input_ms", "stack_ms", "dispatch_ms", "queue_wait_ms"):
        assert on[k] >= 0, k
    assert "idle_by_program_span" not in on
    assert off["program_tracer"] == "off" and off["attempted"] > 0
    assert "spans" not in off


def test_later_on_windows_give_their_counts_alone():
    """Only the first ``on`` window is read in full; the ones after it
    keep their request counts, for timing what the tracer costs."""
    from chipbench import bench
    from chipbench.tests.test_bench_rehearsal import TINY
    spec = bench.cell_spec("alexnet-fp32-b8")
    spec["config"] = dict(TINY, session=spec["config"]["session"],
                          limits=spec["config"]["limits"])
    _, first, second = trace_program.run_windows(
        spec, 7, 0.3, ["on", "on"], {"platform": "cpu"},
        time.perf_counter(), emit=lambda x: 0)
    assert first["spans"] > 0 and first["host_syncs_per_batch"] == 9.0
    assert second["program_tracer"] == "on" and second["attempted"] > 0
    assert "spans" not in second
