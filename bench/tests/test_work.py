"""The work counts against the program's own count and hand counts."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import work.cnn as cnn  # noqa: E402
import work.decoder as dec  # noqa: E402


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_vgg13_forward_flops_match_convproblem_and_hand_count():
    from repro.core.problem import ConvProblem
    cfg = _cfg("vgg13")
    ours = sum(f for kind, f, _ in cnn.conv_calls(cfg, 1) if kind == "fwd")
    theirs = sum(ConvProblem(Nb=n, Nk=k, Nc=c, Nh=h, Nw=w, Nr=r, Ns=s)
                 .flops() for n, c, h, w, k, r, s in cnn.conv_layers(cfg, 1))
    assert ours == theirs
    # hand count: 2*9*(224^2*(3*64 + 64*64) + 112^2*(64*128 + 128*128)
    #   + 56^2*(128*256 + 256*256) + 28^2*(256*512 + 512*512)
    #   + 14^2*2*512*512) = 22.37 GFLOP per image
    hand = 18 * (224 ** 2 * (3 * 64 + 64 * 64) + 112 ** 2 * (64 * 128
                 + 128 * 128) + 56 ** 2 * (128 * 256 + 256 * 256)
                 + 28 ** 2 * (256 * 512 + 512 * 512) + 14 ** 2 * 2 * 512
                 * 512)
    assert ours == hand
    assert abs(cnn.forward_flops(cfg, 1) / 1e9 - 22.37) < 0.01
    # a step: fwd + dKer for every layer, dIn for all but the first
    assert cnn.step_flops(cfg, 64) == 64 * (3 * ours - 18 * 224 ** 2 * 3
                                            * 64 + 6 * 512 * 1000)


def test_smollm_decode_bytes_hand_count():
    cfg = _cfg("smollm-360m")
    d, ff, L, v = 960, 2560, 32, 49152
    per_layer = d * 960 + 2 * d * 320 + 960 * d + 3 * d * ff + 2 * d
    weights = L * per_layer + d + d * v
    assert dec.decode_weight_params(cfg) == weights
    assert dec.kv_bytes_per_token(cfg) == 2 * 32 * 5 * 64 * 2 == 40960
    live = [300, 700]
    want = weights * 2 + 2 * d * 2 + (300 + 700) * 40960 + 2 * 40960
    assert dec.decode_step_bytes(cfg, live) == want
    flops = 2 * 2 * (L * (per_layer - 2 * d) + d * v) \
        + 4 * 15 * 64 * L * 1000
    assert dec.decode_step_flops(cfg, live) == flops
