"""End-to-end trainer: data pipeline -> sharded train step -> checkpoints,
with straggler monitoring and preemption-safe emergency saves.

Two drivers behind ``--mesh``:

* ``--mesh host`` (default): the dense LM trainer on the host GSPMD
  mesh — data pipeline, checkpoints, straggler monitor, SIGTERM saves.
* ``--mesh dist-grid``: the fault-tolerant CNN trainer on the explicit
  ``(Pb,Ph,Pw,Pk,Pc)`` grid (``dist/train.py``
  ``make_resilient_train_loop``): the grid is re-synthesized over the
  visible devices on every (re)start, restore walks back past corrupt
  checkpoints, a watchdog emergency-saves on wedged steps, and
  ``--fault-plan`` injects deterministic failures
  (``fault/inject.py``; runbook ``docs/fault.md``).

CPU-scale runs:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \\
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

  PYTHONPATH=src python -m repro.launch.train --mesh dist-grid \\
      --steps 20 --batch 8 --ckpt-dir /tmp/ckpt \\
      --fault-plan '{"faults": [{"kind": "sigterm", "step": 12}]}'
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt.checkpointer import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticTokens
from repro.fault.monitor import EmergencySaver, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import model_fns
from repro.train.optim import AdamW, cosine_schedule
from repro.train.step import init_train_state, make_train_step


def _load_fault_plan(spec: str):
    """``--fault-plan`` accepts inline JSON or ``@path/to/plan.json``;
    with no flag, the ``REPRO_FAULT_PLAN`` env var is consulted."""
    from repro.fault.inject import FaultPlan
    if not spec:
        return FaultPlan.from_env()
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            spec = f.read()
    return FaultPlan.from_json(spec)


def _main_dist_grid(args):
    """The resilient CNN trainer on the explicit conv grid."""
    from repro.dist.train import (ResilienceConfig,
                                  make_resilient_train_loop,
                                  make_synthetic_cnn_batches)
    from repro.fault.inject import FaultInjector
    from repro.models.cnn import init_cnn

    channels = [int(c) for c in args.channels.split(",")]
    x_shape = (args.batch, args.in_channels, args.hw, args.hw)
    plan = _load_fault_plan(args.fault_plan)
    injector = FaultInjector(plan) if plan is not None else None
    rcfg = ResilienceConfig(
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        watchdog_timeout_s=args.watchdog_timeout or None,
        schedule=args.schedule, minimize=args.minimize,
        fault_log_path=(args.fault_log or None))
    opt = AdamW(lr=args.lr)
    run = make_resilient_train_loop(opt, rcfg, grid="auto",
                                    injector=injector)
    init_params = lambda: init_cnn(
        jax.random.PRNGKey(0), channels=channels,
        n_classes=args.classes, in_channels=x_shape[1])
    batch_fn = make_synthetic_cnn_batches(x_shape, args.classes)
    print(f"[resilient] devices={jax.device_count()} steps={args.steps} "
          f"x={x_shape} channels={channels}", flush=True)
    report = run(init_params, batch_fn, args.steps)
    print(f"[resilient] grid={report['grid']}", flush=True)
    for i, loss in enumerate(report["losses"]):
        print(f"[resilient] step {report['start_step'] + i} "
              f"loss {loss:.6f}", flush=True)
    for ev in report["events"]:
        print(f"[fault] {ev.kind}@{ev.step}: {ev.detail}", flush=True)
    if report["preempted"]:
        print(f"[resilient] preempted at step {report['end_step']} "
              f"(emergency checkpoint committed)", flush=True)
    else:
        print(f"[resilient] done at step {report['end_step']}",
              flush=True)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default="host",
                    choices=("host", "dist-grid"),
                    help="host: dense LM on the GSPMD mesh; dist-grid: "
                         "resilient CNN on the explicit conv grid")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    # dist-grid (resilient) knobs
    ap.add_argument("--channels", default="8,8",
                    help="dist-grid CNN channel widths, comma-separated")
    ap.add_argument("--in-channels", type=int, default=4)
    ap.add_argument("--hw", type=int, default=8,
                    help="dist-grid input spatial extent")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--schedule", default="allgather",
                    choices=("allgather", "ring", "ring2"))
    ap.add_argument("--minimize", default="comm",
                    choices=("comm", "time"),
                    help="grid='auto' objective: analytic wire volume "
                         "or calibrated replay time (CALIB.json)")
    ap.add_argument("--watchdog-timeout", type=float, default=0.0,
                    help="wedged-step watchdog (seconds; 0 disables)")
    ap.add_argument("--fault-plan", default="",
                    help="JSON FaultPlan or @file (fault/inject.py)")
    ap.add_argument("--fault-log", default="",
                    help="JSON-lines FaultEvent log path")
    args = ap.parse_args()

    enable_compile_cache()
    if args.mesh == "dist-grid":
        return _main_dist_grid(args)

    cfg = get_config(args.arch, smoke=args.smoke)
    fns = model_fns(cfg)
    mesh = make_host_mesh()
    print(f"[train] {cfg.arch_id} ({'smoke' if args.smoke else 'full'}) "
          f"mesh={dict(mesh.shape)}")

    params = fns.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {n_params/1e6:.2f}M params")

    opt = AdamW(lr=cosine_schedule(args.lr, warmup=20, total=args.steps))
    state = init_train_state(params, opt)
    step_fn = jax.jit(make_train_step(
        lambda p, b: fns.loss(p, cfg, b), opt,
        n_microbatches=args.microbatches))

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and args.resume:
        restored, step = mgr.restore_latest(state)
        if restored is not None:
            state, start = restored, step
            print(f"[train] resumed from step {step}")

    saver = None
    if mgr:
        saver = EmergencySaver(
            lambda: (mgr.wait(), mgr.save(state, int(state.opt.step)))
        ).install()

    data_cfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                          vocab=cfg.vocab,
                          frames=cfg.family == "encdec",
                          d_model=cfg.d_model,
                          positions3d=cfg.family == "vlm")
    pf = Prefetcher(SyntheticTokens(data_cfg), start_step=start)
    monitor = StragglerMonitor()

    try:
        t_last = time.time()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in pf.next().items()}
            state, metrics = step_fn(state, batch)
            dt = time.time() - t_last
            t_last = time.time()
            if monitor.observe(step, dt):
                print(f"[fault] persistent straggling at step {step} "
                      f"(ema {monitor.stats.ema:.3f}s) — checkpointing")
                if mgr:
                    mgr.save(state, step, async_=True)
                monitor.consecutive = 0
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"dt {dt*1e3:.0f}ms")
            if mgr and step and step % args.ckpt_every == 0:
                mgr.save(state, step, async_=True)
        if mgr:
            mgr.wait()
            mgr.save(state, args.steps)
            print(f"[train] final checkpoint at {args.steps}")
    finally:
        pf.close()
        if saver:
            saver.uninstall()
    print(f"[train] done: final loss {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
