"""Profiler spans inside the serving engine and the train loop, and the
conv phase scopes in the compiled grid train step.

The engine's and the loop's phases are ``jax.profiler`` annotations, so
they land in the same trace as the device's operations.  These tests run
the engine and the loop under ``jax.profiler.trace`` on the CPU and read
the written trace back with ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.serve import ContinuousEngine, _make_requests
from repro.models.api import model_fns

DECODE_CHILDREN = ["serve.decode.launch", "serve.decode.wait",
                   "serve.decode.read", "serve.decode.bookkeep"]
STEP_CHILDREN = ["train.batch", "train.dispatch", "train.sync",
                 "train.after"]
TRAIN_STEPS = 3


def _spans(trace_dir):
    """Host events named ``serve.*`` / ``train.*``, in start order:
    ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1, path
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "train.")):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _children(spans, parent, prefix):
    """Names of the spans starting with ``prefix`` that lie inside
    ``parent``'s interval, in start order."""
    _, s, e, _ = parent
    return [n for n, cs, ce, _ in spans
            if n.startswith(prefix) and (cs, ce) != (s, e)
            and s <= cs and ce <= e]


# --------------------------------------------------------------- serving --

def _serve(trace_dir=None):
    cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                              dtype="float32")
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, params, slots=2, max_seq=24,
                           prefill_bucket=8)
    reqs = _make_requests(cfg, requests=4, prompt_len=6, gen=4, seed=0)
    if trace_dir is None:
        return eng, eng.serve(reqs)
    with jax.profiler.trace(trace_dir):
        stats = eng.serve(reqs)
    return eng, stats


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve-trace"))
    eng, stats = _serve(d)
    return eng, stats, _spans(d)


def _check_admit(eng, stats, spans):
    admits = [sp for sp in spans if sp[0] == "serve.admit"]
    assert sorted(sp[3]["rid"] for sp in admits) == sorted(stats["tokens"])
    for sp in admits:
        assert sp[3]["queued_ms"] >= 0
        assert sp[3]["bucket"] % eng.bucket == 0
        assert _children(spans, sp, "serve.") == ["serve.prefill",
                                                  "serve.scatter"]


def _check_decode_counters(eng, stats, spans):
    decodes = [sp for sp in spans if sp[0] == "serve.decode"]
    assert len(decodes) == len(eng.decode_ms) > 0
    assert [sp[3]["step"] for sp in decodes] == list(range(len(decodes)))
    for sp in decodes:
        assert 1 <= sp[3]["active"] <= eng.slots
        assert sp[3]["queued"] >= 0


def _check_decode_children(eng, stats, spans):
    for sp in (sp for sp in spans if sp[0] == "serve.decode"):
        assert _children(spans, sp, "serve.decode.") == DECODE_CHILDREN


@pytest.mark.parametrize("check", [_check_admit, _check_decode_counters,
                                   _check_decode_children],
                         ids=["admit", "decode_counters", "decode_children"])
def test_engine_spans(served, check):
    check(*served)


# ------------------------------------------------------------ train loop --

def _train(trace_dir=None):
    from repro.dist.train import (ResilienceConfig,
                                  make_resilient_train_loop,
                                  make_synthetic_cnn_batches)
    from repro.models.cnn import init_cnn
    from repro.train.optim import AdamW
    init = lambda: init_cnn(jax.random.PRNGKey(0), channels=[8, 8],
                            n_classes=10, in_channels=4)
    batches = make_synthetic_cnn_batches((8, 4, 8, 8), 10)
    run = make_resilient_train_loop(AdamW(lr=1e-2), ResilienceConfig())
    if trace_dir is None:
        return run(init, batches, TRAIN_STEPS)
    with jax.profiler.trace(trace_dir):
        return run(init, batches, TRAIN_STEPS)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("train-trace"))
    report = _train(d)
    return report, _spans(d)


def test_train_loop_step_spans(trained):
    report, spans = trained
    steps = [sp for sp in spans if sp[0] == "train.step"]
    assert [sp[3]["step_num"] for sp in steps] == list(range(TRAIN_STEPS))
    assert len(report["losses"]) == TRAIN_STEPS
    for sp in steps:
        assert _children(spans, sp, "train.") == STEP_CHILDREN


# ------------------------------------------------ the profiler changes nothing

def test_served_tokens_same_with_profiler_off(served):
    _, traced, _ = served
    _, plain = _serve()
    assert plain["tokens"] == traced["tokens"]
    assert plain["statuses"] == traced["statuses"]


def test_losses_same_with_profiler_off(trained):
    report, _ = trained
    assert _train()["losses"] == report["losses"]


# ----------------------------------------------- conv phases in the HLO --

@pytest.fixture(scope="module")
def grid_step_hlo():
    from repro.dist.conv2d import make_conv_mesh
    from repro.dist.train import init_grid_train_state, make_grid_train_step
    from repro.kernels.autotune import autotune_disabled
    from repro.models.cnn import init_cnn
    from repro.train.optim import AdamW
    opt = AdamW(lr=1e-2)
    params = init_cnn(jax.random.PRNGKey(0), channels=[8, 8], n_classes=10,
                      in_channels=4)
    state = init_grid_train_state(params, opt)
    batch = {"images": jnp.ones((8, 4, 8, 8)),
             "labels": jnp.zeros((8,), jnp.int32)}
    step = jax.jit(make_grid_train_step(opt, make_conv_mesh((1,) * 5)))
    # the persistent cache keys a program without its op_name metadata:
    # a step cached by a tree without the scopes would come back bare
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with autotune_disabled():
            return step.lower(state, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)


@pytest.mark.parametrize("scope", ["conv.fwd", "conv.dx", "conv.dw"])
def test_grid_train_step_hlo_carries_conv_scopes(grid_step_hlo, scope):
    # a scope is one level of the name stack, possibly wrapped by a
    # transformation: ".../conv.dw/...", ".../transpose(jvp(conv.dw))/..."
    level = re.compile(r'op_name="(?:[^"]*[/(])?' + re.escape(scope)
                       + r'[/)"]')
    assert level.search(grid_step_hlo), scope
