#!/usr/bin/env python3
"""Bring-up check: the system's two main paths on the TPU, each compared
with its plain-XLA reference on the same chip.

  python chip_smoke.py             # one chip: CNN training + LM serving
  python chip_smoke.py --chips 4   # the four-chip grids only

One chip (the default) runs two phases in this one process:

* **cnn** — the paper's CNN trainer (``dist.train.make_resilient_train_loop``
  with ``grid="auto"``, as ``launch/train.py --mesh dist-grid`` calls it)
  at the ResNet-50 stage widths 64..512, 224x224, batch 32, f32, 3 steps.
  Every conv reaches ``kernels.ops.local_conv2d`` and the head
  ``local_matmul``.  Reference: the same steps through ``forward_cnn``
  without a mesh (``lax.conv``), plus one step's gradients.  Kernel
  selection is the static paper plan (``autotune_disabled``): timing
  every candidate of the ~24 conv keys compiles each one on its own,
  about 7 minutes of compiles in the v5e rehearsal, more than this
  check's time allows.  Each Pallas kernel the plan picks still compiles
  inside the step, where a refusal raises.
* **lm** — ``launch.serve.run`` on full-width llama3.2-1b (bf16) with
  ``grid=(1,1,1)``: 8 requests, prompt 128, 32 new tokens, 4 slots; every
  projection goes through ``matmul_distributed`` -> ``local_matmul``.
  Reference: the same requests served dense (``grid=None``).

``--chips 4`` runs only what exists across chips: the CNN steps on the grid
``synthesize_cnn_grid`` picks over 4 devices and on a 2.5D ``Pc=2`` grid
under ``schedule="ring2"``, and serving on the grid
``synthesize_serve_grid`` picks — each compared with the one-device
reference computed in this process.

Both sides of every comparison run under ``jax.default_matmul_precision``
set explicitly (``highest`` for the f32 CNN, ``default`` — bf16 products,
f32 accumulation — for the bf16 LM).  Any failed phase or comparison, a
Pallas candidate that failed to compile or run, or a device that is not a
TPU exits non-zero.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

CNN_X = (32, 3, 224, 224)
CNN_CHANNELS = [64, 64, 128, 128, 256, 256, 512, 512]
CNN_CLASSES = 1000
CNN_STEPS = 3
CNN_PRECISION = "highest"
# f32 at "highest" on both sides: the paths differ only in summation order
# (and Winograd's transforms), so losses and gradients agree to ~1e-5.
CNN_LOSS_RTOL = 1e-3
CNN_GRAD_RTOL = 1e-3          # max |g - g_ref| / max |g_ref|, per leaf

LM_ARCH = "llama3.2-1b"
LM_TRAFFIC = dict(requests=8, prompt_len=128, gen=32, slots=4)
LM_PRECISION = "default"
# bf16 weights and activations: the grid path rounds each projection's
# f32 accumulator to bf16 where the dense path rounds its own dot, so 16
# layers of bf16 rounding (2^-8 relative each) separate the logits.
LM_LOGIT_RTOL = 0.05          # max |l - l_ref| / max |l_ref|, per request


def log(msg: str) -> None:
    print(msg, flush=True)


def _max_rel(a, b) -> float:
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                       1e-30))


class Phase:
    """One phase's checks: failures are collected, never raised past."""

    def __init__(self, name: str):
        self.name, self.failures = name, []

    def check(self, ok: bool, what: str) -> None:
        log(f"[{self.name}] {'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)


def report_dispatch(phase: Phase, before: dict) -> None:
    """Every local-kernel key this phase dispatched: the implementation
    chosen, the Pallas candidate a shape rule excluded (and the rule), and
    any candidate that failed to compile or run while it was timed."""
    from repro.kernels import ops as kops
    table = kops.dispatch_table()
    new = {k: v for k, v in table.items() if k not in before}
    for key in sorted(new):
        ent = new[key]
        extra = "".join(f"  excluded {c}: {why}"
                        for c, why in ent["excluded"].items())
        extra += "".join(f"  REFUSED {c}: {err}"
                         for c, err in ent["refused"].items())
        log(f"[{phase.name}] kernel {key} -> {ent['impl']}{extra}")
    refused = {k: v["refused"] for k, v in new.items() if v["refused"]}
    phase.check(not refused, f"no candidate refused by the compiler "
                f"({len(new)} local-kernel keys)")


# ------------------------------------------------------------------ cnn ---

def cnn_case(phase: Phase, *, grid, schedule: str = "allgather",
             x_shape=CNN_X, channels=CNN_CHANNELS, classes=CNN_CLASSES,
             steps: int = CNN_STEPS, seed: int = 0) -> None:
    """Train through ``make_resilient_train_loop`` on ``grid`` and on the
    plain XLA path (``grid=None``), and compare the losses of ``steps``
    steps and the first step's gradient.

    The gradient is read from AdamW's first moment after one step,
    ``m = (1 - b1) * clip * g``: a 1-step run, then the ``steps`` run.
    The second run traces the same program again and finds it in the
    persistent compilation cache, so each path compiles once."""
    import jax

    from repro.dist.conv2d import make_conv_mesh
    from repro.dist.train import (ResilienceConfig,
                                  make_resilient_train_loop,
                                  make_synthetic_cnn_batches)
    from repro.models.cnn import init_cnn
    from repro.train.optim import AdamW

    opt = AdamW(lr=3e-3)
    init = lambda: init_cnn(jax.random.PRNGKey(seed), channels=channels,
                            n_classes=classes, in_channels=x_shape[1])
    batch_fn = make_synthetic_cnn_batches(x_shape, classes, seed=seed)
    rcfg = ResilienceConfig(schedule=schedule)
    out = {}
    with jax.default_matmul_precision(CNN_PRECISION):
        for name, g in (("reference", None), ("grid", grid)):
            t0 = time.perf_counter()
            first = make_resilient_train_loop(opt, rcfg, grid=g)(
                init, batch_fn, 1)
            t1 = time.perf_counter()
            rep = make_resilient_train_loop(opt, rcfg, grid=g)(
                init, batch_fn, steps)
            t2 = time.perf_counter()
            out[name] = (rep, first["state"].opt.m)
            where = ("lax.conv, no mesh" if g is None else
                     f"grid {rep['grid']} schedule {schedule} on "
                     f"{make_conv_mesh(rep['grid']).devices.size} device(s)")
            log(f"[{phase.name}] {name} ({where}): losses {rep['losses']}; "
                f"compile + 1 step {t1 - t0:.1f}s, {steps} steps "
                f"(cached program) {t2 - t1:.1f}s")
    (rep, m1), (ref, m1_ref) = out["grid"], out["reference"]
    batch = batch_fn(0)
    for name, arr in (("params[head]", rep["state"].params["head"]),
                      ("images", batch["images"])):
        log(f"[{phase.name}] {name} on devices "
            f"{sorted(d.id for d in arr.sharding.device_set)}")
    loss_err = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(rep["losses"], ref["losses"]))
    phase.check(len(rep["losses"]) == steps == len(ref["losses"])
                and loss_err <= CNN_LOSS_RTOL,
                f"loss max rel err {loss_err:.3e} <= {CNN_LOSS_RTOL} "
                f"(precision {CNN_PRECISION})")
    errs = jax.tree_util.tree_map(_max_rel, m1, m1_ref)
    worst = max(jax.tree_util.tree_leaves(errs))
    phase.check(worst <= CNN_GRAD_RTOL,
                f"grad max rel err {worst:.3e} <= {CNN_GRAD_RTOL} "
                f"(worst leaf, step-0 gradients)")


# ------------------------------------------------------------------- lm ---

def lm_case(phase: Phase, *, grid, arch: str = LM_ARCH, cfg=None,
            traffic=LM_TRAFFIC, seed: int = 0, params=None, ref=None):
    """Serve the request set through ``launch.serve.run`` on ``grid`` and
    compare prefill logits and greedy tokens with the dense engine."""
    import jax

    from repro.configs import get_config
    from repro.launch import serve
    from repro.models.api import model_fns

    cfg = cfg or get_config(arch)
    if params is None:
        params = model_fns(cfg).init(jax.random.PRNGKey(seed), cfg)
    kw = dict(traffic, seed=seed, params=params, keep_logits=True,
              warmup=True)
    with jax.default_matmul_precision(LM_PRECISION):
        if ref is None:
            t0 = time.perf_counter()
            ref = serve.run(cfg, grid=None, **kw)
            log(f"[{phase.name}] reference (dense): "
                f"{ref['n_tokens']} tokens, total "
                f"{time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        res = serve.run(cfg, grid=grid, **kw)
        total_s = time.perf_counter() - t0
    log(f"[{phase.name}] {cfg.arch_id} grid {res['grid']}: "
        f"{res['n_requests']} requests, {res['n_tokens']} tokens, "
        f"statuses {sorted(set(res['statuses'].values()))}; compile + "
        f"warmup {total_s - res['wall_s']:.1f}s, serve {res['wall_s']:.1f}s")
    for rid in sorted(res["tokens"])[:2]:
        log(f"[{phase.name}] request {rid} tokens {res['tokens'][rid]}")
    errs = [_max_rel(res["prefill_logits"][r], ref["prefill_logits"][r])
            for r in sorted(ref["prefill_logits"])]
    phase.check(len(errs) == traffic["requests"]
                and res["n_ok"] == traffic["requests"]
                and max(errs) <= LM_LOGIT_RTOL,
                f"prefill logits max rel err {max(errs):.3e} <= "
                f"{LM_LOGIT_RTOL} over {len(errs)} requests (bf16, "
                f"precision {LM_PRECISION})")
    same = sum(a == b for r in ref["tokens"]
               for a, b in zip(res["tokens"].get(r, []), ref["tokens"][r]))
    total = sum(len(t) for t in ref["tokens"].values())
    log(f"[{phase.name}] greedy tokens agree with dense at {same}/{total} "
        f"positions (near-ties may flip, docs/serving.md)")
    return params, ref


# --------------------------------------------------------------- main ---

def _static_plan(fn):
    """Run ``fn`` on the static paper-plan kernel choice (see module doc)."""
    from repro.kernels import autotune

    def run():
        with autotune.autotune_disabled():
            return fn()
    return run


def one_chip() -> list:
    from repro.kernels import ops as kops
    cnn, lm = Phase("cnn"), Phase("lm")
    for phase, fn in ((cnn, _static_plan(lambda: cnn_case(cnn, grid="auto"))),
                      (lm, lambda: lm_case(lm, grid=(1, 1, 1)))):
        before = kops.dispatch_table()
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — reported, then the next phase
            traceback.print_exc()
            phase.failures.append("raised")
        report_dispatch(phase, before)
        log(f"[{phase.name}] phase {time.perf_counter() - t0:.1f}s")
    return [cnn, lm]


def four_chips() -> list:
    import jax

    from repro.dist.conv2d import make_conv_mesh
    from repro.kernels import ops as kops
    cnn, cnn25, lm = Phase("cnn4"), Phase("cnn4-2.5d"), Phase("lm4")
    mesh = make_conv_mesh((1, 1, 1, 1, len(jax.devices())))
    log(f"[mesh] make_mesh over {mesh.devices.size} devices: "
        f"{[d.id for d in mesh.devices.flat]}")
    # Pc=2 cannot split 3 input channels, so the 2.5D grid trains the
    # same network on 4-channel images; its reference is computed apart
    x4 = (CNN_X[0], 4) + CNN_X[2:]
    cases = (
        (cnn, _static_plan(lambda: cnn_case(cnn, grid="auto"))),
        (cnn25, _static_plan(lambda: cnn_case(
            cnn25, grid=(2, 1, 1, 1, 2), schedule="ring2", x_shape=x4))),
        (lm, lambda: lm_case(lm, grid="auto")),
    )
    for phase, fn in cases:
        before = kops.dispatch_table()
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            phase.failures.append("raised")
        report_dispatch(phase, before)
        log(f"[{phase.name}] phase {time.perf_counter() - t0:.1f}s")
    return [cnn, cnn25, lm]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: CNN training + LM serving on one chip; "
                         "4: the multi-chip grids only")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"the repository root", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX sees platform {dev.platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    phases = one_chip() if args.chips == 1 else four_chips()
    failed = [f"{p.name}: {f}" for p in phases for f in p.failures]
    if failed:
        print("chip_smoke: FAILED " + "; ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
