"""Driver for CNN training cells: the program's resilient trainer,
``dist.train.make_resilient_train_loop(..., grid="auto")``, as
``launch/train.py --mesh dist-grid`` calls it.

One call of the loop does everything: its first steps trace and compile
the step (twice: for the initial state and for the committed state the
step returns), then it runs back to back.  The benchmark's ``batch_fn``
is the only hook: the window opens when the loop asks for the batch of
step ``WARM_STEPS`` and closes at the first request for a batch after
``--seconds`` have passed, which ends the loop.  So the window holds whole
steps, each with the loop's own host work (batch, step, ``float(loss)``).

Correctness: the loop's state after its first step and after its third
(read from the loop's frame when it asks for the next batch) and its
first three losses are compared with the plain reference
(``bench/reference/cnn.py``) run from the same weights on the same
batches:

* ``first_loss_gap``: the relative gap of the first loss, computed from
  the same weights and batch on both sides (the later steps' losses also
  carry AdamW's amplification of round-off, see ``PERF.md``);
* ``loss_gap``: the largest relative gap of the three losses;
* ``grad_gap``: the first clipped gradient, ``m / (1 - b1)`` of AdamW's
  state after one step, by the worst leaf: the gap between the program's
  and the reference's norm of that leaf, over the larger of the
  reference's norm of the leaf and of the median leaf;
* ``change_gap``: the same for each leaf's change over the three steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out (they move by round-off alone).
"""

from __future__ import annotations

import sys
import time

import numpy as np

import generator
import harness

WARM_STEPS = 4          # steps 0 and 1 compile; 2 and 3 run warm
CAPTURE = (1, 3)        # states after one and after three steps


class WindowClosed(Exception):
    """Raised from ``batch_fn`` to end the loop when the window closes."""


def make_params(cfg: dict, seed: int):
    """The weights, in the program's layout, on the device in one jitted
    call: He-normal convolutions, zero biases, N(0, 1/C) head."""
    import jax
    import jax.numpy as jnp

    k, cin0 = cfg["kernel_size"], cfg["in_channels"]
    chans, ncls = cfg["channels"], cfg["num_classes"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(chans) + 1)
        convs, cin = [], cin0
        for i, cout in enumerate(chans):
            std = (2.0 / (cin * k * k)) ** 0.5
            convs.append({
                "w": std * jax.random.normal(keys[i], (cout, cin, k, k),
                                             jnp.float32),
                "b": jnp.zeros((cout,), jnp.float32)})
            cin = cout
        head = jax.random.normal(keys[-1], (cin, ncls), jnp.float32)
        return {"convs": convs, "head": head * cin ** -0.5}

    return make, generator.jax_key(seed, 0)


def _leaf_norms():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: jnp.stack([jnp.linalg.norm(x.ravel())
                                        for x in jax.tree.leaves(t)]))


def _change_norms(make):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(params, key):
        p0 = make(key)
        return jnp.stack([jnp.linalg.norm((a - b).ravel()) for a, b in
                          zip(jax.tree.leaves(params), jax.tree.leaves(p0))])
    return f


LOOP_LOCALS = ("state", "losses", "step_fn")


def _loop_locals(frame) -> dict:
    """The calling loop's locals that the benchmark reads (the program
    has no public per-step hook yet; ``tests/test_harness.py`` pins
    these names)."""
    loc = frame.f_locals
    missing = [k for k in LOOP_LOCALS if k not in loc]
    if missing:
        raise RuntimeError(f"the train loop's frame holds no {missing}; "
                           "the benchmark reads them from it")
    return loc


def gaps(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf: |norm_prog - norm_ref| / max(norm_ref, median norm)."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    den = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / den))


def reference_run(cfg, mix, seed, precision, *, half_batch=False) -> dict:
    """The reference's three steps from the seed's weights and batches:
    its losses and the per-leaf norms of its first clipped gradient and
    of each leaf's change.  ``half_batch`` feeds it only the first half of
    each batch (a fault planted in the reference put in the program's
    place)."""
    import jax
    import jax.numpy as jnp

    import reference.cnn as ref
    make, key = make_params(cfg, seed)
    batch_fn = generator.image_batch_fn(mix, seed)
    batches = []
    for s in range(3):
        b = batch_fn(s)
        if half_batch:
            n = b["labels"].shape[0] // 2
            b = {k: v[:n] for k, v in b.items()}
        batches.append(b)
    p0 = make(key)
    chunk = mix.get("reference_chunk", 0)
    if half_batch and chunk:
        chunk //= 2
    losses, g1, p3 = ref.train_steps(p0, batches, cfg["optimizer"],
                                     pool_every=cfg["pool_every"],
                                     precision=precision, chunk=chunk)
    norms = _leaf_norms()
    return {"losses": losses, "grad_norms": np.asarray(norms(g1)),
            "change_norms": np.asarray(norms(jax.tree.map(jnp.subtract,
                                                          p3, p0)))}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared.  Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out of ``change_gap``."""
    g_ref = np.asarray(ref["grad_norms"], float)
    keep = g_ref >= 1e-3 * np.median(g_ref)
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    return {"first_loss_gap": float(lg[0]), "loss_gap": float(max(lg)),
            "grad_gap": gaps(prog["grad_norms"], g_ref),
            "change_gap": gaps(prog["change_norms"], ref["change_norms"],
                               keep)}


def detail(prog: dict, ref: dict) -> dict:
    """Diagnostics beside ``compare``: each step's loss gap and, for the
    two norm gaps, the worst leaf's index and each leaf's gap."""
    def per_leaf(p, q):
        p, q = np.asarray(p, float), np.asarray(q, float)
        return np.abs(p - q) / np.maximum(q, np.median(q))
    g = per_leaf(prog["grad_norms"], ref["grad_norms"])
    c = per_leaf(prog["change_norms"], ref["change_norms"])
    return {"loss_gaps": [abs(a - b) / abs(b) for a, b in
                          zip(prog["losses"], ref["losses"])],
            "grad_worst_leaf": int(np.argmax(g)), "grad_leaf_gaps":
            [float(x) for x in g], "change_worst_leaf": int(np.argmax(c)),
            "change_leaf_gaps": [float(x) for x in c]}


def run(r: harness.Run) -> harness.Record:
    import jax

    from repro.dist.train import ResilienceConfig, make_resilient_train_loop
    from repro.train.optim import AdamW

    cfg, mix = r.config, r.mix
    o = cfg["optimizer"]
    opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
    make, key = make_params(cfg, r.seed)
    params = make(key)
    batch_fn = generator.image_batch_fn(mix, r.seed)
    norms, change = _leaf_norms(), _change_norms(make)
    captured = {}
    times = {}

    def feed(step: int):
        if step <= WARM_STEPS and step not in times.get("feed", {}):
            times.setdefault("feed", {})[step] = time.monotonic() - r.t_start
        if step in CAPTURE and step not in captured:
            loc = _loop_locals(sys._getframe(1))
            st = loc["state"]
            if step == 1:
                m = norms(st.opt.m)
                captured[1] = True
                captured["grad_norms"] = np.asarray(m) / (1 - o["b1"])
            else:
                captured[3] = True
                captured["change_norms"] = np.asarray(change(st.params, key))
                captured["losses"] = list(loc["losses"][:3])
        if step == WARM_STEPS and "t0" not in times:
            r.tracer.start()
            times["c0"] = harness.compiles()
            times["t0"], times["k0"] = time.monotonic(), step
        elif "t0" in times and time.monotonic() - times["t0"] >= r.seconds:
            times["t1"], times["k1"] = time.monotonic(), step
            times["c1"] = harness.compiles()
            loc = _loop_locals(sys._getframe(1))
            times["losses"] = list(loc["losses"])
            times["program"] = (loc["step_fn"], loc["state"])
            raise WindowClosed
        with r.tracer.span("bench.batch_fn"):
            return batch_fn(step)

    loop = make_resilient_train_loop(
        opt, ResilienceConfig(pool_every=cfg["pool_every"]),
        grid=mix["grid"])
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        try:
            loop(lambda: params, feed, 1 << 30)
        except WindowClosed:
            pass
        finally:
            r.tracer.stop()
        # the window's step program, as compiled (read from the cache):
        # its footprint, and with --trace 1 its convolutions
        step_fn, st = times.pop("program")
        compiled = step_fn.lower(st, batch_fn(0)).compile()
        step_bytes = harness.program_bytes(compiled)
        conv_ops = []
        if r.tracer.on:
            import trace_reduce
            conv_ops = trace_reduce.conv_instructions(compiled.as_text())
        del step_fn, st, compiled
    del params
    print("set-up: batch asked for at " + ", ".join(
        f"step {k} {v:.1f}s" for k, v in sorted(times["feed"].items())),
        file=sys.stderr)
    steps = times["k1"] - times["k0"]
    win = times["t1"] - times["t0"]
    window_losses = times["losses"][times["k0"]:times["k1"]]
    failed = sum(1 for x in window_losses if not np.isfinite(x))
    mem = max(harness.memory_peak(r.devices), step_bytes)
    batch = mix["batch"]
    import work.cnn as wk
    ref = reference_run(cfg, mix, r.seed, cfg["matmul_precision"])
    checks = compare(captured, ref)
    return harness.Record(
        end_to_end={"setup_s": times["t0"] - r.t_start,
                    "train_images_per_s": steps * batch / win},
        attempted=steps, failed=failed, checks=checks,
        memory_peak_bytes=mem, trace=r.tracer.trace, window_s=win,
        window_programs=harness.lowered_between(times["c0"], times["c1"]),
        layer={"steps": steps, "batch": batch, "chips": len(r.devices),
               "config": cfg,
               "step_flops": wk.step_flops(cfg, batch),
               "conv_ops": conv_ops,
               "detail": detail(captured, ref),
               "conv_least_s": wk.conv_least_time_s(
                   cfg, batch, r.peaks["bf16_flops_per_s"],
                   r.peaks["hbm_bytes_per_s"]),
               "peaks": r.peaks})
