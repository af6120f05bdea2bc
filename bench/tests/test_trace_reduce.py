"""The trace reduction on hand-made traces and on small traces recorded on
a TPU v5e (``bench/tests/data/*.json.gz``: a few training steps of
``vgg13-imagenet-b64`` and a few serving steps of
``smollm360m-chat-poisson``, cut to their first executions)."""

from __future__ import annotations

import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def _trace():
    """One device; window 0-100 ms; a conv 10-30, a fused op 25-40, an
    all-reduce 35-60 (exposed 40-60), a module run 5-70 and one 80-120
    that leaves the window."""
    ops = [["convolution.1", 10 * MS, 20 * MS],
           ["fusion.2", 25 * MS, 15 * MS],
           ["all-reduce.3", 35 * MS, 25 * MS]]
    mods = [["jit_train_step(1)", 5 * MS, 65 * MS],
            ["jit_train_step(1)", 80 * MS, 40 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.batch_fn", 60 * MS, 30 * MS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host}


def test_device_summary_by_hand():
    s = tr.device_summary(_trace())["/device:TPU:0"]
    assert s["busy_s"] == pytest.approx(0.050)          # 10..60
    assert s["collective_s"] == pytest.approx(0.025)
    assert s["exposed_collective_s"] == pytest.approx(0.020)   # 40..60
    assert tr.window_s(_trace()) == pytest.approx(0.100)


def test_runs_and_conv_inside_runs():
    t = _trace()
    assert tr.module_runs(t, "jit_train_step") == (1, pytest.approx(0.065))
    n, conv = tr.conv_in_runs(t, "jit_train_step", ["convolution.1"])
    assert n == 1 and conv == pytest.approx(0.020)


HLO = """HloModule jit_f

%fused_computation.7 (param_0: f32[2,8,8,4]) -> f32[2,8,8,4] {
  %param_0 = f32[2,8,8,4]{3,2,1,0} parameter(0)
  ROOT %convolution.3 = f32[2,8,8,4]{3,2,1,0} convolution(%param_0, %param_0), window={size=3x3}
}

%fused_computation.9 (param_0.1: f32[4]) -> f32[4] {
  ROOT %add.1 = f32[4]{0} add(%param_0.1, %param_0.1)
}

ENTRY %main.5 (x: f32[2,8,8,4]) -> f32[2,8,8,4] {
  %x = f32[2,8,8,4]{3,2,1,0} parameter(0)
  %fusion.5 = f32[2,8,8,4]{3,2,1,0} fusion(%x), kind=kOutput, calls=%fused_computation.7
  %fusion.8 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.9
  %_lambda_.2 = f32[2,8,8,4]{3,2,1,0} custom-call(%x), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"CONV"}}
  %_lambda_.3 = f32[2,8,8,4]{3,2,1,0} custom-call(%x), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"MATMUL"}}
  ROOT %convolution.9 = f32[2,8,8,4]{3,2,1,0} convolution(%x, %x), window={size=3x3}
}
"""


def test_conv_instructions_from_hlo():
    import base64
    conv = base64.b64encode(b"ML\x00func _conv_kernel\x00").decode()
    mm = base64.b64encode(b"ML\x00func _matmul_kernel\x00").decode()
    names = tr.conv_instructions(HLO.replace("CONV", conv)
                                  .replace("MATMUL", mm))
    assert names == ["_lambda_.2", "convolution.9", "fusion.5"]
    assert tr.instruction("%fusion.5 = f32[2] fusion(%x), kind=kLoop") \
        == "fusion.5"


def test_idle_gaps_named_by_host_span():
    gaps = dict(tr.idle_gaps(_trace()))
    # idle 0-10 (no span), 60-100 (bench.batch_fn covers 60-90)
    assert gaps["bench.batch_fn"] == pytest.approx(0.040)
    assert gaps["host"] == pytest.approx(0.010)


def _brute_union(iv):
    """Covered length by a sweep over +1/-1 edges."""
    edges = sorted([(s, 1) for s, _ in iv] + [(e, -1) for _, e in iv])
    total, depth, last = 0, 0, None
    for x, step in edges:
        if depth > 0:
            total += x - last
        depth += step
        last = x
    return total


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.json.gz")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path):
    t = tr.read(path)
    assert t["devices"], "the recorded trace has a device plane"
    win = tr.window_s(t)
    s0, e0 = tr.window(t)
    for dev, d in tr.device_summary(t).items():
        ops = t["devices"][dev]["ops"]
        clipped = [(max(s, s0), min(s + dur, e0)) for _, s, dur in ops
                   if s + dur > s0 and s < e0]
        assert d["busy_s"] == pytest.approx(_brute_union(clipped) * 1e-9)
        assert 0 < d["busy_s"] <= win
    top = tr.top_ops(t)
    assert top and all(v > 0 for _, v in top)
    idle = sum(v for _, v in tr.idle_gaps(t))
    busy = tr.device_summary(t)[sorted(t["devices"])[0]]["busy_s"]
    assert idle == pytest.approx(win - busy, rel=1e-6)


def test_recorded_vgg_conv_roofline_reads_below_peak():
    """Two VGG-13 steps recorded on a v5e: the convolutions named by the
    compiled HLO take most of each step, and their roofline share is a
    share (0-100%)."""
    import json

    import work.cnn as wk
    t = tr.read(os.path.join(HERE, "data", "vgg13-b64-2steps.json.gz"))
    n, conv = tr.conv_in_runs(t, "jit_train_step", t["conv_ops"])
    runs, dev = tr.module_runs(t, "jit_train_step")
    assert n == runs == 2
    assert 0.5 * dev < conv < dev
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "vgg13.json")) as f:
        cfg = json.load(f)
    least = wk.conv_least_time_s(cfg, 64, 197e12, 819e9)
    assert 0 < 100 * n * least / conv < 100
