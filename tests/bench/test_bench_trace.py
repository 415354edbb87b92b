"""The trace -> metric reduction, on hand-made traces and on a small trace
recorded on a TPU v5 lite (``bench/tools/record_trace.py``: three ticks of
a program with scopes ``manage.eval``, ``bank.payload`` and
``manage.retrain`` and the ``tbs_step_gather`` kernel)."""
import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace(ops, spans=(), devices=1):
    return tr.Trace(ops=[tr.Op(*o) for o in ops],
                    spans=[tr.Span(*s) for s in spans], devices=devices)


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = _trace([(0, "m", "a", 0, 10), (0, "m", "b", 5, 10),
                (0, "m", "c", 30, 10), (0, "m", "d", 95, 20)])
    # [0,15) + [30,40) + [95,100) inside the window [0,100)
    assert tr.busy_s(t, 0, 100) == pytest.approx(30e-9)
    assert tr.busy_s(t, 10, 35) == pytest.approx(10e-9)


def test_busy_is_averaged_over_chips():
    t = _trace([(0, "m", "a", 0, 50), (1, "m", "a", 0, 10)], devices=2)
    assert tr.busy_s(t, 0, 100) == pytest.approx(30e-9)


def test_scope_kernel_and_module_time():
    t = _trace([(0, "jit_f", "fusion.1", 0, 10), (0, "jit_f", "fusion.2",
                                                   10, 5),
                (0, "jit_f", "tbs_step_gather.3", 15, 7),
                (0, "jit_g", "fusion.1", 30, 4)])
    scopes = {"jit_f": {"fusion.1": "jit(f)/manage.eval/dot",
                        "fusion.2": "jit(f)/manage.retrain/cond/x",
                        "tbs_step_gather.3": "jit(f)/bank.payload/pallas"}}
    assert tr.scope_s(t, scopes, "manage.eval", 0, 100) == pytest.approx(
        10e-9)
    assert tr.scope_s(t, scopes, "manage.retrain", 0, 100) == \
        pytest.approx(5e-9)
    assert tr.scope_s(t, scopes, "manage", 0, 100) == 0
    assert tr.kernel_s(t, "tbs_step_gather", 0, 100) == pytest.approx(7e-9)
    assert tr.module_s(t, "jit_g", 0, 100) == pytest.approx(4e-9)
    top = tr.top_ops(t, scopes, 0, 100)
    assert top[0] == ["jit_f:manage.eval:fusion", 10e-9]


def test_nested_ops_count_once():
    # a loop spans its body's ops; the scope's time is the loop's, not the
    # loop's plus its body's, and the loop's own time is what its body
    # leaves
    t = _trace([(0, "jit_f", "while.1", 0, 40), (0, "jit_f", "fusion.2", 5, 10),
                (0, "jit_f", "fusion.3", 20, 15),
                (0, "jit_f", "fusion.4", 50, 10)])
    scopes = {"jit_f": {"while.1": "jit(f)/manage.retrain/while",
                        "fusion.2": "jit(f)/manage.retrain/while/body/dot",
                        "fusion.3": "jit(f)/manage.retrain/while/body/add",
                        "fusion.4": "jit(f)/manage.eval/dot"}}
    assert tr.scope_s(t, scopes, "manage.retrain", 0, 100) == \
        pytest.approx(40e-9)
    assert tr.module_s(t, "jit_f", 0, 100) == pytest.approx(50e-9)
    assert tr.self_times(t, 0, 100) == [15, 10, 15, 10]
    top = dict(tr.top_ops(t, scopes, 0, 100))
    assert top["jit_f:manage.retrain:while"] == pytest.approx(15e-9)
    assert top["jit_f:manage.retrain:fusion"] == pytest.approx(25e-9)


def test_idle_gaps_are_labelled_by_the_host_span_over_them():
    t = _trace([(0, "m", "a", 10, 10), (0, "m", "b", 60, 30)],
               spans=[("bench.window", 0, 100), ("bench.tick", 0, 50),
                      ("bench.ack", 20, 25), ("bench.dispatch", 50, 12)])
    gaps = dict(tr.idle_gaps(t, 0, 100))
    # [0,10) in the tick; [20,60): ack to 45, tick to 50, dispatch to 60;
    # [90,100) under no span
    assert gaps["bench.ack"] == pytest.approx(25e-9)
    assert gaps["bench.tick"] == pytest.approx(15e-9)
    assert gaps["bench.dispatch"] == pytest.approx(10e-9)
    assert gaps["(no span)"] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(
        100e-9 - tr.busy_s(t, 0, 100))


def test_scope_map_reads_hlo_metadata():
    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused (p: f32[4]) -> f32[4] {
  ROOT %tanh.1 = f32[4]{0} tanh(%p), metadata={op_name="jit(step)/manage.eval/tanh"}
}

ENTRY %main {
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/manage.eval/tanh" source_file="a.py"}
  ROOT %tbs_step_gather.1 = s32[1]{0} custom-call(%y), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bank.payload/pallas_call"}
}
"""
    module, names = tr.scope_map(text)
    assert module == "jit_step"
    assert names["fusion.3"] == "jit(step)/manage.eval/tanh"
    assert names["tbs_step_gather.1"].split("/")[1] == "bank.payload"


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(str(DATA / "trace_small.xplane.pb"))
    module, names = tr.scope_map((DATA / "trace_small.hlo.txt").read_text())
    return trace, {module: names}


def test_recorded_trace_has_device_ops_and_host_spans(recorded):
    trace, scopes = recorded
    assert trace.devices == 1 and len(trace.ops) > 20
    ticks = [s for s in trace.spans if s.name == "bench.tick"]
    assert len(ticks) == 3
    assert {o.module for o in trace.ops} >= {"jit_step"}
    assert list(scopes) == ["jit_step"]


def test_recorded_trace_reduces(recorded):
    trace, scopes = recorded
    ticks = [s for s in trace.spans if s.name == "bench.tick"]
    w0, w1 = ticks[0].start, ticks[-1].start + ticks[-1].dur
    busy = tr.busy_s(trace, w0, w1)
    assert 0 < busy < (w1 - w0) / 1e9
    k = tr.kernel_s(trace, "tbs_step_gather", w0, w1)
    assert k > 0
    ev = tr.scope_s(trace, scopes, "manage.eval", w0, w1)
    rt = tr.scope_s(trace, scopes, "manage.retrain", w0, w1)
    pay = tr.scope_s(trace, scopes, "bank.payload", w0, w1)
    assert ev > 0 and rt > 0 and pay >= k
    assert ev + rt + pay <= tr.module_s(trace, "jit_step", w0, w1) + 1e-12
    gaps = tr.idle_gaps(trace, w0, w1)
    assert sum(g for _, g in gaps) == pytest.approx((w1 - w0) / 1e9 - busy,
                                                    rel=1e-6)
    assert {n for n, _ in gaps} <= {"bench.tick", "bench.dispatch",
                                    "bench.host_wait", "(no span)"}
    top = tr.top_ops(trace, scopes, w0, w1)
    assert 0 < len(top) <= 10 and top[0][1] >= top[-1][1]
