"""The harness driven end to end on the CPU at small sizes, past its look
for a chip: a sound run is correct, and each fault planted in the timed
path makes ``correct`` come out false.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import tiny  # noqa: E402

TRAIN = "vgg13-imagenet-b64"
SERVE = "smollm360m-chat-poisson"
SEED = 2 ** 31 + 12345


@pytest.fixture
def run(monkeypatch):
    harness = tiny.patch(monkeypatch)
    harness.prepare_process()
    import jax

    import run_cell

    def go(workload, seconds=2.0, fault=None, trace=False):
        return run_cell.execute(workload, SEED, seconds, trace, fault=fault,
                                devices=jax.devices()[:1])
    return go


def test_sound_training_run_is_correct(run):
    rec, res = run(TRAIN)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_images_per_s"}
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"


def test_sound_serving_run_is_correct(run):
    rec, res = run(SERVE, seconds=3.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                   "ttft_p95_ms", "itl_p95_ms"}
    assert rec.layer["checked_tokens"] > 0
    assert res["window_compiles"] == 0


def test_train_loop_keeps_the_locals_the_benchmark_reads():
    """The training driver reads the loop's ``state``, ``losses`` and
    ``step_fn`` from its frame (the program has no public per-step hook):
    a rename in ``dist/train.py`` fails here, by name."""
    import inspect

    import harness
    import repro.dist.train as dt
    drv = harness.load_module("drivers", "train_cnn")
    consts = dt.make_resilient_train_loop.__code__.co_consts
    run = [c for c in consts if inspect.iscode(c) and c.co_name == "run"]
    assert run, "make_resilient_train_loop defines no inner 'run'"
    missing = [k for k in drv.LOOP_LOCALS if k not in run[0].co_varnames]
    assert not missing, (f"the train loop no longer names {missing}; "
                         "bench/drivers/train_cnn.py reads them")


def test_memory_reading_holds_the_step_program(run):
    rec, res = run(TRAIN)
    assert res["device"]["memory_peak_bytes"] > 0


def _broken_step(monkeypatch, how):
    import repro.dist.train as dt
    orig = dt.make_grid_train_step

    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch):
            if how == "unchanged":
                _, metrics = step(state, batch)
                return state, metrics
            n = batch["labels"].shape[0] // 2
            return step(state, {k_: v[:n] for k_, v in batch.items()})
        return broken
    monkeypatch.setattr(dt, "make_grid_train_step", make)


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_training_fault_is_caught(run, monkeypatch, how):
    _broken_step(monkeypatch, how)
    _, res = run(TRAIN)
    assert not res["correct"], res["checks"]


def _token_altered(kind, engine):
    import jax
    import jax.numpy as jnp
    orig = engine._decode_fn
    engine._decode_fn = jax.jit(
        lambda p, c, t: (lambda lc: (jnp.roll(lc[0], 1, axis=-1), lc[1]))(
            orig(p, c, t)))


def _state_unchanged(kind, engine):
    import jax
    orig = engine._decode_fn
    engine._decode_fn = jax.jit(lambda p, c, t: (orig(p, c, t)[0], c))


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_serving_fault_is_caught(run, fault):
    _, res = run(SERVE, seconds=3.0, fault=fault)
    assert not res["correct"], res["checks"]


def test_no_chip_no_result(monkeypatch, capsys):
    """Without an accelerator the command fails and prints no result."""
    import jax

    import run_cell
    if jax.devices()[0].platform != "cpu":
        pytest.skip("an accelerator is present")
    assert run_cell.main(["--workload", TRAIN, "--seed", "1",
                          "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""
