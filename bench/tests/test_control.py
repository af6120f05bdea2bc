"""The controls fail the cells' limits: the plain reference put in the
program's place, at the precision below the configuration's, at the
cells' widths.

* Serving: the reference with float8 e4m3 matmul operands picks tokens
  whose gap below the float32 reference's best exceeds ``token_gap``'s
  limit (full SmolLM-360M, three sequences of 256 tokens; runs on any
  device, about a minute on a CPU).
* Training: the reference at ``high`` (three bf16 passes) instead of
  ``highest`` reads over one of the limits (VGG-13 at 224x224, batch 8;
  needs a TPU, where ``high`` differs from ``highest``).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def _limits(cell):
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def test_serving_control_fails_token_gap():
    harness.prepare_process()
    import jax.numpy as jnp

    drv = harness.load_module("drivers", "serve_lm")
    cfg = harness.load_json("configs", "smollm-360m.json")
    make, key = drv.make_params(cfg, 2 ** 31 + 7)
    params = make(key)
    rng = np.random.default_rng(0)
    seqs = [(list(rng.integers(0, cfg["vocab_size"], 128)),
             list(rng.integers(0, cfg["vocab_size"], 128))) for _ in range(3)]
    del jnp
    gaps = drv.reference_gaps(cfg, params, seqs, 256, quant="fp8")
    assert drv.widest(gaps) > _limits("smollm360m-chat-poisson")["token_gap"]


def test_training_control_fails_a_limit():
    harness.prepare_process()
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("'high' and 'highest' differ only on a TPU")
    drv = harness.load_module("drivers", "train_cnn")
    cfg = harness.load_json("configs", "vgg13.json")
    mix = dict(harness.load_json("traffic", "imagenet-b64.json"), batch=8)
    ref = drv.reference_run(cfg, mix, 5, "highest")
    ctl = drv.reference_run(cfg, mix, 5, "high")
    lim = _limits("vgg13-imagenet-b64")
    nums = drv.compare(ctl, ref)
    assert any(nums[k] > lim[k] for k in lim), nums
