"""The one traffic generator: reads a mix file of ``bench/traffic/`` and
makes the cell's inputs from ``--seed``.

Two kinds of mix:

* ``train_batches`` -- a closed loop of synthetic image batches: every
  step's images and labels come from (seed, step), made on the device in
  one jitted call.
* ``requests`` -- open-loop arrivals.  The work is the same for every
  seed: prompt and output lengths are the stratified quantiles of the
  mix's lognormals and the inter-arrival gaps the stratified quantiles of
  its arrival process, interleaved in one fixed order (drawn once from a
  constant).  The seed draws the prompts' token ids.  When a long request
  arrives decides how many of its tokens fall inside the window, so a
  seed that reordered the requests would change the work measured.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


def key_words(seed: int, *salt: int) -> np.ndarray:
    """Two uint32 words from a seed of any size (more than 32 bits hold)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *salt])
    return ss.generate_state(2, np.uint32)


def jax_key(seed: int, *salt: int):
    import jax
    return jax.random.wrap_key_data(key_words(seed, *salt),
                                    impl="threefry2x32")


# ------------------------------------------------------------- training --

def image_batch_fn(mix: dict, seed: int):
    """``batch_fn(step) -> {"images": [N,C,H,W] f32, "labels": [N] i32}``,
    a deterministic function of (seed, step) computed on the default
    device."""
    import jax
    import jax.numpy as jnp

    n = mix["batch"]
    shape = (n, mix["channels"], mix["image_size"], mix["image_size"])
    classes = mix["classes"]
    base = jax_key(seed, 1)

    @jax.jit
    def make(key, step):
        kx, ky = jax.random.split(jax.random.fold_in(key, step))
        return {"images": jax.random.normal(kx, shape, jnp.float32),
                "labels": jax.random.randint(ky, (n,), 0, classes,
                                             jnp.int32)}

    # the key is an argument, not a constant: one program for every seed
    return lambda step: make(base, jnp.int32(step))


# -------------------------------------------------------------- serving --

ORDER_SEED = 20210527     # the fixed interleaving of sizes and gaps


@dataclass
class Arrival:
    rid: int
    due_s: float          # offset from the window start
    prompt: List[int]
    max_new: int


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal: ``median``,
    ``sigma`` (of the log), clipped to [``min``, ``max``]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _stratified(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(vals), spec["min"], spec["max"]).astype(int)


def arrival_gaps(spec: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) of the mix's arrival process.

    ``poisson``: exponential gaps at ``rate_per_s``.  ``onoff``: bursts of
    ``burst`` requests at ``burst_rate_per_s`` separated by silences, with
    the long-run mean ``rate_per_s``."""
    rate = spec["rate_per_s"]
    q = _stratified(n)
    if spec["process"] == "poisson":
        return -np.log1p(-q) / rate
    if spec["process"] == "onoff":
        burst, fast = spec["burst"], spec["burst_rate_per_s"]
        gaps = -np.log1p(-q) / fast
        silence = burst / rate - burst / fast
        if silence < 0:
            raise ValueError("burst_rate_per_s must exceed rate_per_s")
        gaps[::burst] += silence
        return gaps
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def request_schedule(mix: dict, seed: int, seconds: float, vocab: int
                     ) -> List[Arrival]:
    """The requests of one run: as many as the rate brings in the mix's
    pre-roll (``preroll_s``, due before the window opens, so that the
    window finds the engine in its steady state) and the window of
    ``seconds``, each with its due time (negative in the pre-roll),
    prompt ids and output length."""
    arr = mix["arrivals"]
    pre = float(mix.get("preroll_s", 0.0))
    span = pre + seconds
    n = max(1, int(round(arr["rate_per_s"] * span)))
    order = np.random.default_rng(ORDER_SEED)
    plens = order.permutation(lognormal_lengths(mix["prompt_len"], n))
    olens = order.permutation(lognormal_lengths(mix["output_len"], n))
    gaps = arrival_gaps(arr, n)
    if arr["process"] == "poisson":
        gaps = order.permutation(gaps)
    rng = np.random.default_rng(key_words(seed, 2))
    # first request due at the start of the pre-roll; the n arrivals
    # fill the pre-roll and the window
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due = due * (span / max(due[-1] + gaps[-1], 1e-9)) - pre
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(plens[i]))
        out.append(Arrival(rid=i, due_s=float(due[i]),
                           prompt=[int(t) for t in toks],
                           max_new=int(olens[i])))
    return out


def prefill_buckets(mix: dict) -> List[int]:
    """The padded prompt lengths this mix can produce."""
    b = mix["engine"]["prefill_bucket"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    return sorted({math.ceil(p / b) * b for p in range(lo, hi + 1)})
