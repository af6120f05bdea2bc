"""Small stand-ins for the benchmark's configurations and mixes, for
running a whole cell's harness on the CPU in the tests: the same keys and
code paths, at sizes a test can hold."""

from __future__ import annotations

import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def configs():
    vgg = _load("configs", "vgg13.json")
    vgg.update(image_size=16, channels=[8, 8, 16, 16], num_classes=10)
    lm = _load("configs", "smollm-360m.json")
    lm.update(program_smoke=True, hidden_size=60, intermediate_size=96,
              num_hidden_layers=2, num_attention_heads=3,
              num_key_value_heads=1, head_dim=20, vocab_size=256,
              dtype="float32", initializer_range=0.3)
    return {"vgg13": vgg, "smollm-360m": lm}


def mixes():
    b64 = _load("traffic", "imagenet-b64.json")
    b64.update(batch=4, image_size=16, classes=10)
    chat = _load("traffic", "chat-poisson.json")
    chat.update(preroll_s=1.0,
                arrivals={"process": "poisson", "rate_per_s": 8.0},
                prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                            "min": 4, "max": 32},
                output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 2, "max": 16},
                engine={"slots": 4, "max_seq": 48, "prefill_bucket": 16,
                        "grid": "auto"},
                check={"requests": 3})
    return {"imagenet-b64": b64, "chat-poisson": chat}


def patch(monkeypatch):
    """Make the harness read the small stand-ins by the real names."""
    sys.path.insert(0, BENCH)
    import harness
    real = harness.load_json
    cfgs, mx = configs(), mixes()

    def load_json(*parts):
        name = parts[-1][:-len(".json")] if parts[-1].endswith(".json") \
            else parts[-1]
        if parts[0] == "configs" and name in cfgs:
            return copy.deepcopy(cfgs[name])
        if parts[0] == "traffic" and name in mx:
            return copy.deepcopy(mx[name])
        return real(*parts)

    monkeypatch.setattr(harness, "load_json", load_json)
    return harness
