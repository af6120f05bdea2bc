"""The program-span readers (``bench/spans.py`` and the readers of
``serve.queue_wait_p95_ms``, ``serve.admit_ms``, ``serve.decode_read_ms``,
``serve.decode_bookkeep_ms``, ``train.input_ms``, ``train.conv_dw_ms``) on
hand-made traces, on a serving trace recorded on a TPU v5e with the
program's spans (``data/smollm360m-chat-spans.json.gz``: two admissions
and two decode steps, cut from a traced run of the cell), and on the
harness run on the CPU; and the numbers the existing reductions give on
the recorded traces, pinned, so that keeping program spans in a trace
changes none of them."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spans  # noqa: E402
import tiny  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000
SEED = 2 ** 31 + 4242


def _read(metric, trace, **layer):
    rec = harness.Record(end_to_end={}, attempted=0, failed=0, checks={},
                         memory_peak_bytes=0, layer=layer, trace=trace)
    return harness.load_module("metrics", metric).read(rec)


def _serve_trace():
    """Window 0-100 ms.  Two admissions (queued 5 and 40 ms; 10 and 20 ms
    long) and two decode steps, each with launch, wait, read and bookkeep;
    a third admission (queued 90 ms) starts inside the window and ends
    after it.  The device runs 8-14 and 20-30 (prefills), 37-46 and 62-75
    (decode steps)."""
    ops = [["prefill", 8 * MS, 6 * MS], ["prefill", 20 * MS, 10 * MS],
           ["decode", 37 * MS, 9 * MS], ["decode", 62 * MS, 13 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.admit", 5 * MS, 30 * MS],
            ["bench.decode", 35 * MS, 25 * MS],
            ["bench.decode", 60 * MS, 30 * MS],
            ["bench.admit", 95 * MS, 10 * MS]]
    sp = [["serve.admit", 5 * MS, 10 * MS, {"rid": 1, "queued_ms": 5.0}],
          ["serve.admit", 15 * MS, 20 * MS, {"rid": 2, "queued_ms": 40.0}],
          ["serve.decode", 35 * MS, 25 * MS, {"step": 0}],
          ["serve.decode.launch", 35 * MS, 1 * MS, {}],
          ["serve.decode.wait", 36 * MS, 10 * MS, {}],
          ["serve.decode.read", 46 * MS, 10 * MS, {}],
          ["serve.decode.bookkeep", 56 * MS, 4 * MS, {}],
          ["serve.decode", 60 * MS, 28 * MS, {"step": 1}],
          ["serve.decode.launch", 60 * MS, 2 * MS, {}],
          ["serve.decode.wait", 62 * MS, 13 * MS, {}],
          ["serve.decode.read", 75 * MS, 6 * MS, {}],
          ["serve.decode.bookkeep", 81 * MS, 7 * MS, {}],
          ["serve.admit", 95 * MS, 10 * MS, {"rid": 3, "queued_ms": 90.0}]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": host, "spans": sp}


def test_serve_readers_by_hand():
    t = _serve_trace()
    # the rid-3 admission leaves the window: two admissions count
    assert _read("serve.queue_wait_p95_ms", t) == pytest.approx(
        5 + 0.95 * 35)
    assert _read("serve.admit_ms", t) == pytest.approx(15.0)
    assert _read("serve.decode_read_ms", t) == pytest.approx(8.0)
    assert _read("serve.decode_bookkeep_ms", t) == pytest.approx(5.5)


def test_train_readers_by_hand():
    ops = [["convolution.1", 10 * MS, 20 * MS], ["fusion.2", 30 * MS, 5 * MS],
           ["convolution.1", 60 * MS, 20 * MS], ["fusion.2", 80 * MS, 5 * MS]]
    mods = [["jit_train_step(1)", 10 * MS, 25 * MS],
            ["jit_train_step(1)", 60 * MS, 25 * MS]]
    t = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
         "host": [["bench.window", 0, 100 * MS]],
         "spans": [["train.batch", 2 * MS, 4 * MS, {}],
                   ["train.batch", 40 * MS, 6 * MS, {}],
                   ["train.batch", 90 * MS, 20 * MS, {}]]}
    # the third batch leaves the window
    assert _read("train.input_ms", t) == pytest.approx(5.0)
    scoped = {"conv.dw": ["fusion.2"], "conv.fwd": ["convolution.1"]}
    assert _read("train.conv_dw_ms", t, scoped_ops=scoped,
                 chips=1) == pytest.approx(5.0)


@pytest.mark.parametrize("metric", spans.READERS)
def test_readers_read_nothing_without_spans(metric):
    """A trace that ``trace_reduce.load`` reduced holds no program spans
    and its record no scoped instructions: every reader returns None."""
    t = _serve_trace()
    del t["spans"]
    assert _read(metric, t, chips=1) is None
    assert _read(metric, None, chips=1) is None


def test_decode_counters_over_the_window():
    t = _serve_trace()
    t["spans"][2][3].update(active=3, queued=0)
    t["spans"][7][3].update(active=5, queued=2)
    assert spans.decode_counters(t) == {"active": [3, 4, 5],
                                        "queued": [0, 1, 2]}
    assert spans.decode_counters(_recorded_without_spans()) == {}


def test_idle_by_span_takes_the_innermost_span():
    t = _serve_trace()
    idle = dict(spans.idle_by_span(t))
    # idle 0-8, 14-20, 30-37, 46-62, 75-100 ms.  Innermost: host 0-5 and
    # 90-95; admissions 5-8, 14-20, 30-35, 95-100; launch 35-36 and
    # 60-62; wait 36-37; read 46-56 and 75-81; bookkeep 56-60 and 81-88;
    # bench.decode 88-90
    assert idle == pytest.approx({
        "host": 0.010, "serve.admit": 0.019, "serve.decode.launch": 0.003,
        "serve.decode.wait": 0.001, "serve.decode.read": 0.016,
        "serve.decode.bookkeep": 0.011, "bench.decode": 0.002})
    assert sum(idle.values()) == pytest.approx(
        tr.window_s(t) - tr.device_summary(t)["/device:TPU:0"]["busy_s"])
    # without program spans, the harness's spans take their place
    del t["spans"]
    assert dict(spans.idle_by_span(t)) == pytest.approx({
        "host": 0.010, "bench.admit": 0.019, "bench.decode": 0.033})


HLO = """HloModule jit_train_step

%fused_computation.7 (param_0: f32[2,8,8,4]) -> f32[2,8,8,4] {
  %param_0 = f32[2,8,8,4]{3,2,1,0} parameter(0)
  ROOT %convolution.3 = f32[2,8,8,4]{3,2,1,0} convolution(%param_0, %param_0), window={size=3x3}, metadata={op_name="jit(train_step)/transpose(jvp())/conv.dw/conv_general_dilated"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(train_step)/conv.dw/reduce_sum"}
}

ENTRY %main.5 (x: f32[2,8,8,4]) -> f32[2,8,8,4] {
  %x = f32[2,8,8,4]{3,2,1,0} parameter(0)
  %fusion.5 = f32[2,8,8,4]{3,2,1,0} fusion(%x), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/transpose(jvp())/conv.dw/conv_general_dilated"}
  %fusion.6 = f32[2,8,8,4]{3,2,1,0} fusion(%x), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/transpose(jvp(conv.dw))/transpose"}
  %reduce.4 = f32[] reduce(%x, %x), to_apply=%region_0.1, metadata={op_name="jit(train_step)/conv.dwx/reduce_sum"}
  %convolution.8 = f32[2,8,8,4]{3,2,1,0} convolution(%x, %x), window={size=3x3}, metadata={op_name="jit(train_step)/jvp(conv.fwd)/conv_general_dilated"}
  ROOT %convolution.9 = f32[2,8,8,4]{3,2,1,0} convolution(%x, %x), window={size=3x3}, metadata={op_name="jit(train_step)/transpose(jvp())/conv.dx"}
}
"""


@pytest.mark.parametrize("scope,names", [
    ("conv.dw", ["fusion.5", "fusion.6"]),
    ("conv.fwd", ["convolution.8"]),
    ("conv.dx", ["convolution.9"])])
def test_scoped_instructions(scope, names):
    assert spans.scoped_instructions(HLO, scope) == names


# ------------------------------------------------------ recorded traces --

def _recorded_without_spans():
    return _recorded("smollm360m-chat-2steps.json.gz")


def _recorded(name):
    return tr.read(os.path.join(HERE, "data", name))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_recorded_serve_spans_nest_and_read():
    """Two admissions and two decode steps recorded on a v5e with the
    program's spans: the phases nest in order inside their parents, the
    readers read them, and the idle time split by innermost span adds up
    to the window's idle time."""
    t = _recorded("smollm360m-chat-spans.json.gz")
    sp = sorted(t["spans"], key=lambda x: (x[1], -x[2]))

    def inside(parent, prefix):
        s, e = parent[1], parent[1] + parent[2]
        return [x[0] for x in sp if x[0].startswith(prefix)
                and x is not parent and s <= x[1] and x[1] + x[2] <= e]
    decodes = [x for x in sp if x[0] == "serve.decode"]
    admits = [x for x in sp if x[0] == "serve.admit"]
    assert len(decodes) == 2 and admits
    for d in decodes:
        assert inside(d, "serve.decode.") == [
            "serve.decode.launch", "serve.decode.wait", "serve.decode.read",
            "serve.decode.bookkeep"]
        assert {"step", "active", "queued"} <= set(d[3])
    for a in admits:
        assert inside(a, "serve.") == ["serve.prefill", "serve.scatter"]
    assert _read("serve.decode_read_ms", t) == pytest.approx(28.0426535)
    assert _read("serve.decode_bookkeep_ms", t) == pytest.approx(88.977965)
    assert _read("serve.queue_wait_p95_ms", t) >= 0
    idle = spans.idle_by_span(t, n=100)
    busy = tr.device_summary(t)["/device:TPU:0"]["busy_s"]
    assert sum(v for _, v in idle) == pytest.approx(tr.window_s(t) - busy)
    assert idle[0][0] == "serve.decode.bookkeep"


def test_recorded_vgg_reductions_pinned():
    """The numbers the reductions and readers gave on the recorded VGG
    trace before the program had spans."""
    import work.cnn as wk
    t = _recorded("vgg13-b64-2steps.json.gz")
    assert tr.window_s(t) == pytest.approx(0.624697934, rel=1e-9)
    assert tr.idle_share(t) == pytest.approx(1.1270717600932567, rel=1e-9)
    assert tr.module_runs(t, "jit_train_step") == (
        2, pytest.approx(0.617325781, rel=1e-9))
    assert tr.conv_in_runs(t, "jit_train_step", t["conv_ops"]) == (
        2, pytest.approx(0.5101683020000001, rel=1e-9))
    assert tr.top_ops(t, 3) == [
        ["broadcast_maximum_fusion", pytest.approx(0.059295383, rel=1e-9)],
        ["multiply_reduce_fusion.9", pytest.approx(0.058916741, rel=1e-9)],
        ["fusion.110", pytest.approx(0.034851628, rel=1e-9)]]
    assert tr.idle_gaps(t) == [
        ["bench.batch_fn", pytest.approx(0.004257114, rel=1e-9)],
        ["host", pytest.approx(0.00278368, rel=1e-6)]]
    least = wk.conv_least_time_s(_config("vgg13"), 64,
                                 PEAKS["bf16_flops_per_s"],
                                 PEAKS["hbm_bytes_per_s"])
    roof = _read("train.conv_roofline", t, conv_ops=t["conv_ops"],
                 conv_least_s=least)
    assert roof == pytest.approx(100 * 2 * least / 0.5101683020000001)
    assert _read("train.device_idle_share", t) == pytest.approx(
        1.1270717600932567, rel=1e-9)
    assert _read("train.collective_exposed_share", t, chips=1) is None


def test_recorded_serve_reductions_pinned():
    """The numbers the reductions and readers gave on the recorded serving
    trace before the program had spans."""
    t = _recorded("smollm360m-chat-2steps.json.gz")
    assert tr.window_s(t) == pytest.approx(0.429360957, rel=1e-9)
    assert tr.idle_share(t) == pytest.approx(20.006557792351852, rel=1e-9)
    assert tr.module_runs(t, "jit__decode") == (
        2, pytest.approx(0.307295522, rel=1e-9))
    assert tr.top_ops(t, 3) == [
        ["copy.3", pytest.approx(0.0240025, rel=1e-9)],
        ["fusion.712", pytest.approx(0.007011745, rel=1e-9)],
        ["fusion.713", pytest.approx(0.007009628, rel=1e-9)]]
    assert tr.idle_gaps(t) == [
        ["bench.decode", pytest.approx(0.047921621, rel=1e-6)],
        ["bench.admit", pytest.approx(0.037978727, rel=1e-6)]]
    assert _read("serve.device_idle_share", t) == pytest.approx(
        20.006557792351852, rel=1e-9)
    assert all(_read(m, t) is None for m in spans.READERS[:4])


# ------------------------------------------------- the harness, on the CPU --

@pytest.fixture
def traced(monkeypatch):
    """A traced run that compiles: the persistent cache's key leaves out
    the scopes' ``op_name`` metadata, so a step compiled from an older tree
    would be read back without them."""
    tiny.patch(monkeypatch)
    harness.prepare_process()
    import jax

    def go(workload, seconds):
        return spans.traced_run(workload, SEED, seconds,
                                devices=jax.devices()[:1])
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield go
    jax.config.update("jax_enable_compilation_cache", prior)


def test_traced_serving_run_reads_the_engine_spans(traced):
    rec, res = traced("smollm360m-chat-poisson", 3.0)
    assert res["correct"], res["checks"]
    assert set(res["spans"]) == set(spans.READERS[:4])
    assert 1 <= res["decode_counters"]["active"][2] <= 4     # 4 slots
    admits = spans.in_window(rec.trace, "serve.admit")
    decodes = spans.in_window(rec.trace, "serve.decode")
    assert admits and decodes
    assert all(sp[3]["queued_ms"] >= 0 for sp in admits)
    # consecutive steps; the trace runs on through the drain after the
    # window, so it holds at least the steps the window counted
    steps = [sp[3]["step"] for sp in decodes]
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    assert len(decodes) >= len(rec.layer["decode_ms"])


def test_traced_training_run_keeps_the_conv_scopes(traced):
    rec, res = traced("vgg13-imagenet-b64", 2.0)
    assert res["correct"], res["checks"]
    assert "train.input_ms" in res["spans"]
    scoped = rec.layer["scoped_ops"]
    assert all(scoped[s] for s in spans.SCOPES), scoped
    steps = spans.in_window(rec.trace, "train.step")
    assert [sp[3]["step_num"] for sp in steps] == list(
        range(steps[0][3]["step_num"], steps[0][3]["step_num"] + len(steps)))
