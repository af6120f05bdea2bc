"""The traffic generator: every seed gets the same work."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import generator  # noqa: E402


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_seeds_share_sizes_and_gaps():
    mix = _mix("chat-poisson")
    pre = mix["preroll_s"]
    a = generator.request_schedule(mix, 2 ** 31 + 5, 40.0, 49152)
    b = generator.request_schedule(mix, 12345678901, 40.0, 49152)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"]
                                     * (pre + 40))
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt[:4] for r in a] != [r.prompt[:4] for r in b]
    gaps = np.diff([r.due_s for r in a])
    assert 0.5 < np.mean(gaps) * mix["arrivals"]["rate_per_s"] < 1.5
    for s in (a, b):
        assert s[0].due_s == -pre and s[-1].due_s < 40.0
        in_window = sum(1 for r in s if r.due_s >= 0)
        assert 0.5 < in_window / (mix["arrivals"]["rate_per_s"] * 40) < 1.5
        assert all(mix["prompt_len"]["min"] <= len(r.prompt)
                   <= mix["prompt_len"]["max"] for r in s)
        assert all(len(r.prompt) + r.max_new
                   <= mix["engine"]["max_seq"] for r in s)


def test_same_seed_same_requests():
    mix = _mix("chat-poisson")
    a = generator.request_schedule(mix, 7, 10.0, 49152)
    b = generator.request_schedule(mix, 7, 10.0, 49152)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]


def test_prefill_buckets():
    assert generator.prefill_buckets(_mix("chat-poisson")) == [256, 512, 768,
                                                                1024]
