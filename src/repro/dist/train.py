"""Grid-parallel CNN training: the full train step through ``repro.dist``.

This is the paper's algorithms doing the job they were derived for —
Demmel & Dinh (2018) and Chen et al. (2022) state their communication
bounds for the *combined* forward + backward CNN computation, and the
``repro.dist`` ops carry custom VJPs whose backward passes transpose the
forward schedule on the same ``(Pb, Ph, Pw, Pk, Pc)`` grid.  The train
step built here therefore runs loss, gradients and the AdamW update with
every conv (and the classifier head matmul) on explicit-grid distributed
ops; no GSPMD sharding specs are involved.

``cnn_train_comm_elems`` walks the same layer structure as
``models.cnn.forward_cnn`` and sums the analytic per-device fwd+bwd wire
volumes of the distributed *ops* (``conv_train_comm_elems`` /
``matmul_train_comm_elems``).  Each per-op total matches the compiled
HLO of that op at ratio 1.0 (``make grad-test``); a whole compiled train
step additionally pays inter-layer resharding that XLA inserts between
ops (a conv emits Out as ``P(b,k,h,w)`` while the next conv consumes
``P(b,(c,k),h,w)``, so grids with ``Pc > 1`` re-split the channel dim
between layers — ~25-30% extra wire on the 8-device 2.5D acceptance
grid).  Accounting for (or eliminating, by chaining the c-subshard
layout forward) that reshard traffic is a ROADMAP follow-up.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.sharding import Mesh

from repro.dist.conv2d import (conv_grid_divides,
                               conv_train_comm_elems, conv_train_mem_elems)
from repro.dist.matmul import (matmul_grid_divides,
                               matmul_train_comm_elems,
                               matmul_train_mem_elems)
from repro.models.cnn import loss_cnn
from repro.train.optim import AdamW
from repro.train.step import TrainState, init_train_state, make_train_step


def make_grid_train_step(optimizer: AdamW, mesh: Mesh, *,
                         schedule: str = "allgather",
                         save_gathered: bool = False,
                         pool_every: int = 2,
                         n_microbatches: int = 1,
                         loss_fn: Optional[Callable] = None) -> Callable:
    """Train step (``(state, batch) -> (state, metrics)``) for the CNN on
    an explicit 5-axis conv mesh.

    ``schedule`` picks the dist-op schedule (``allgather`` / ``ring`` /
    ``ring2``); ``save_gathered=True`` trades backward memory for zero
    gather-replay wire.  ``loss_fn(params, batch, dist_mesh=...,
    dist_schedule=..., dist_save_gathered=...)`` may be supplied to train
    a different model through the dist ops; it defaults to
    ``models.cnn.loss_cnn``.
    """
    base = loss_fn if loss_fn is not None else functools.partial(
        loss_cnn, pool_every=pool_every)
    loss = functools.partial(base, dist_mesh=mesh, dist_schedule=schedule,
                             dist_save_gathered=save_gathered)
    return make_train_step(loss, optimizer,
                           n_microbatches=n_microbatches, mode="dist-grid")


def init_grid_train_state(params, optimizer: AdamW) -> TrainState:
    """Plain (uncompressed) train state for the grid-parallel step."""
    return init_train_state(params, optimizer, compress=False)


def _cnn_layer_shapes(x_shape, channels: List[int], *, k: int,
                      pool_every: int) -> List[Tuple[tuple, tuple]]:
    """(x_shape, w_shape) per conv layer, mirroring ``forward_cnn``."""
    N, C, H, W = x_shape
    out = []
    cin = C
    for i, cout in enumerate(channels):
        out.append(((N, cin, H, W), (cout, cin, k, k)))
        cin = cout
        if (i + 1) % pool_every == 0:
            H, W = H // 2, W // 2
    return out


def cnn_train_comm_elems(x_shape, channels: List[int], n_classes: int,
                         grid, *, k: int = 3, pool_every: int = 2,
                         schedule: str = "allgather",
                         save_gathered: bool = False) -> Dict:
    """Analytic per-device fwd+bwd wire volume (elements) of the dist ops
    in one CNN train step on ``grid = (Pb, Ph, Pw, Pk, Pc)`` — one entry
    per conv layer plus the head matmul (0 when its shapes don't divide
    the matmul view and it falls back to a dense GSPMD matmul).  ``total``
    covers the ops only; a compiled train step adds inter-layer reshard
    collectives on top (see module docstring)."""
    if len(grid) != 5:
        raise ValueError(f"conv grid must be (Pb,Ph,Pw,Pk,Pc), got {grid}")
    layers = []
    for xs, ws in _cnn_layer_shapes(x_shape, channels, k=k,
                                    pool_every=pool_every):
        layers.append(conv_train_comm_elems(xs, ws, grid,
                                            schedule=schedule,
                                            save_gathered=save_gathered))
    pb, ph, pw, pk, pc = grid
    mm_grid = (pb * ph * pw, pk, pc)
    N, cin = x_shape[0], channels[-1]
    if matmul_grid_divides(N, cin, n_classes, mm_grid):
        head = matmul_train_comm_elems(N, cin, n_classes, mm_grid,
                                       save_gathered=save_gathered)
    else:
        head = {"fwd": {"total": 0.0}, "bwd": {"total": 0.0}, "total": 0.0}
    total = sum(l["total"] for l in layers) + head["total"]
    return {"layers": layers, "head": head, "total": total,
            "fwd_total": sum(l["fwd"]["total"] for l in layers)
            + head["fwd"]["total"],
            "bwd_total": sum(l["bwd"]["total"] for l in layers)
            + head["bwd"]["total"]}


def cnn_train_mem_elems(x_shape, channels: List[int], n_classes: int,
                        grid, *, k: int = 3, pool_every: int = 2,
                        schedule: str = "allgather",
                        save_gathered: bool = False) -> Dict:
    """Analytic per-device peak live memory (elements) of the dist ops in
    one CNN train step: the per-layer peaks (``conv_train_mem_elems`` /
    ``matmul_train_mem_elems``) and their max — layers execute one after
    another, so the step peak is the worst layer, not the sum."""
    if len(grid) != 5:
        raise ValueError(f"conv grid must be (Pb,Ph,Pw,Pk,Pc), got {grid}")
    layers = []
    for xs, ws in _cnn_layer_shapes(x_shape, channels, k=k,
                                    pool_every=pool_every):
        layers.append(conv_train_mem_elems(xs, ws, grid, schedule=schedule,
                                           save_gathered=save_gathered))
    pb, ph, pw, pk, pc = grid
    mm_grid = (pb * ph * pw, pk, pc)
    N, cin = x_shape[0], channels[-1]
    if matmul_grid_divides(N, cin, n_classes, mm_grid):
        head = matmul_train_mem_elems(N, cin, n_classes, mm_grid,
                                      schedule=schedule,
                                      save_gathered=save_gathered)
    else:
        head = {"peak": 0.0}
    peak = max([l["peak"] for l in layers] + [head["peak"]])
    return {"layers": layers, "head": head, "peak": peak}


def grid_divides_cnn(x_shape, channels: List[int], grid, *, k: int = 3,
                     pool_every: int = 2) -> bool:
    """True when every conv layer of the CNN satisfies the runtime
    divisibility constraints of ``conv2d_distributed`` on ``grid``."""
    return all(conv_grid_divides(xs, ws, grid)
               for xs, ws in _cnn_layer_shapes(x_shape, channels, k=k,
                                               pool_every=pool_every))


# ===================================================== resilient loop ====
#
# The preemption-safe, elastic, watchdogged driver around the grid train
# step: CheckpointManager (crc32-verified, falls back past corrupt
# steps) + EmergencySaver (SIGTERM) + StepWatchdog (wedged collectives)
# + StragglerMonitor + FaultInjector hooks, with the grid re-synthesized
# over whatever devices survive a restart (ROADMAP item 5; runbook in
# docs/fault.md).


@dataclasses.dataclass
class ResilienceConfig:
    """Knobs of :func:`make_resilient_train_loop`.

    ``ckpt_dir=""`` disables checkpointing (then SIGTERM/wedge still
    log events but nothing is saved); ``watchdog_timeout_s=None``
    disables the wedge watchdog.
    """

    ckpt_dir: str = ""
    ckpt_every: int = 5
    keep: int = 3
    watchdog_timeout_s: Optional[float] = None
    schedule: str = "allgather"
    save_gathered: bool = False
    pool_every: int = 2
    minimize: str = "comm"   # grid="auto" objective: "comm" | "time"
    straggler_z: float = 3.0
    straggler_patience: int = 3
    fault_log_path: Optional[str] = None


def make_synthetic_cnn_batches(x_shape, n_classes: int, *,
                               seed: int = 0) -> Callable[[int], Dict]:
    """Deterministic ``batch_fn(step)`` — the same step always yields
    the same batch, in the original run and in every resumed run, so a
    restarted trajectory is comparable to an uninterrupted one."""
    import jax

    def batch_fn(step: int) -> Dict:
        key = jax.random.PRNGKey(seed * 1_000_003 + step)
        kx, ky = jax.random.split(key)
        return {"images": jax.random.normal(kx, tuple(x_shape)),
                "labels": jax.random.randint(ky, (x_shape[0],), 0,
                                             n_classes)}
    return batch_fn


def make_resilient_train_loop(optimizer: AdamW, rcfg: ResilienceConfig,
                              *, grid=None,
                              loss_fn: Optional[Callable] = None,
                              injector=None) -> Callable:
    """Build ``run(init_params_fn, batch_fn, steps) -> report`` — the
    fault-tolerant CNN train loop on the explicit conv grid.

    ``grid``: a ``(Pb,Ph,Pw,Pk,Pc)`` tuple, ``"auto"`` (re-synthesized
    over ``jax.device_count()`` via ``synthesize_cnn_grid`` — the
    elastic path: a restart on fewer devices picks a new grid and the
    chunked checkpoint re-shards onto it), or ``None`` (dense
    reference on the default device; identical loop semantics, which is
    what makes killed-and-resumed trajectories comparable to an
    uninterrupted dense run).

    ``batch_fn(step)`` must be deterministic in ``step``
    (:func:`make_synthetic_cnn_batches`, or the data pipeline's
    ``batch_at`` contract) — resume re-reads exactly the batches the
    lost steps would have seen.

    The returned report dict: ``state``, ``losses`` (one per executed
    step), ``start_step``/``end_step``, ``grid``, ``preempted`` (True
    when a SIGTERM stopped the loop after the emergency save), and
    ``events`` (the structured :class:`FaultEvent` list).

    Under ``jax.profiler`` each iteration is a ``train.step`` step span
    (``step_num``) holding ``train.batch``, ``train.dispatch``,
    ``train.sync`` (the ``float(loss)`` wait) and ``train.after`` (the
    straggler monitor and checkpoint saves).
    """
    import jax

    from repro.ckpt.checkpointer import CheckpointManager
    from repro.dist.conv2d import make_conv_mesh
    from repro.fault.monitor import EmergencySaver, StragglerMonitor
    from repro.fault.watchdog import FaultEvent, FaultLog, StepWatchdog

    def run(init_params_fn: Callable[[], Dict],
            batch_fn: Callable[[int], Dict], steps: int) -> Dict:
        log = FaultLog(rcfg.fault_log_path)
        if injector is not None:
            injector.log = log  # injected faults land in the report
        mgr = (CheckpointManager(rcfg.ckpt_dir, keep=rcfg.keep)
               if rcfg.ckpt_dir else None)
        state = init_grid_train_state(init_params_fn(), optimizer)
        start = 0
        if mgr is not None:
            restored, meta_step = mgr.restore_latest(
                state, on_corrupt=lambda s, e: log.emit(FaultEvent(
                    kind="corrupt_ckpt", step=s, detail=str(e))))
            if restored is not None:
                state, start = restored, int(meta_step)

        # ---- grid resolution (the elastic re-synthesis point) -------
        if grid == "auto":
            if loss_fn is not None:
                raise ValueError(
                    "grid='auto' introspects the CNN params; pass an "
                    "explicit grid with a custom loss_fn")
            from repro.core.sharding_synthesis import synthesize_cnn_grid
            probe = batch_fn(start)
            x_shape = tuple(probe["images"].shape)
            channels = [b["w"].shape[0] for b in state.params["convs"]]
            n_classes = state.params["head"].shape[1]
            choice = synthesize_cnn_grid(
                x_shape, channels, n_classes, jax.device_count(),
                pool_every=rcfg.pool_every, schedule=rcfg.schedule,
                minimize=rcfg.minimize)
            grid_t = choice.grid
            log.emit(FaultEvent(
                kind="elastic_plan", step=start,
                detail=f"grid {grid_t} over {jax.device_count()} "
                       f"devices ({choice.algo})"))
        else:
            grid_t = tuple(grid) if grid is not None else None

        if grid_t is not None:
            mesh = make_conv_mesh(grid_t)
            step_fn = jax.jit(make_grid_train_step(
                optimizer, mesh, schedule=rcfg.schedule,
                save_gathered=rcfg.save_gathered,
                pool_every=rcfg.pool_every, loss_fn=loss_fn))
        else:
            base = loss_fn if loss_fn is not None else functools.partial(
                loss_cnn, pool_every=rcfg.pool_every)
            step_fn = jax.jit(make_train_step(base, optimizer))

        # ---- emergency save machinery -------------------------------
        # `holder` is the last COMPLETED state; the saver and watchdog
        # threads read it while the main thread may be stuck in a
        # wedged step.  `save_lock` serializes every save path.
        holder = {"state": state, "done": start}
        save_lock = threading.Lock()

        def emergency_save(reason: str) -> None:
            if mgr is None:
                return
            with save_lock:
                mgr.wait()
                mgr.save(holder["state"], holder["done"])

        saver = EmergencySaver(lambda: (
            log.emit(FaultEvent(kind="sigterm", step=holder["done"],
                                detail="emergency checkpoint at "
                                       f"step {holder['done']}")),
            emergency_save("sigterm"))).install()
        wd = (StepWatchdog(rcfg.watchdog_timeout_s,
                           on_wedge=lambda s, dt: emergency_save("wedge"),
                           log=log)
              if rcfg.watchdog_timeout_s else None)
        monitor = StragglerMonitor(z=rcfg.straggler_z,
                                   patience=rcfg.straggler_patience)
        ctx = {"ckpt_root": rcfg.ckpt_dir, "log": log}

        losses: List[float] = []
        preempted = False
        try:
            for step in range(start, steps):
                if saver.triggered:
                    preempted = True
                    break
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=step):
                    if wd is not None:
                        wd.arm(step)
                    try:
                        if injector is not None:
                            injector.fire("step", step, ctx)
                        if saver.triggered:  # injected/real SIGTERM landed
                            preempted = True
                            break
                        with jax.profiler.TraceAnnotation("train.batch"):
                            batch = batch_fn(step)
                        t0 = time.monotonic()
                        with jax.profiler.TraceAnnotation("train.dispatch"):
                            state, metrics = step_fn(state, batch)
                        with jax.profiler.TraceAnnotation("train.sync"):
                            loss = float(metrics["loss"])  # blocks
                    finally:
                        if wd is not None:
                            wd.disarm()
                    with jax.profiler.TraceAnnotation("train.after"):
                        dt = time.monotonic() - t0
                        losses.append(loss)
                        holder["state"], holder["done"] = state, step + 1
                        if monitor.observe(step, dt):
                            log.emit(FaultEvent(
                                kind="straggler", step=step,
                                detail=f"dt {dt:.3f}s vs ema "
                                       f"{monitor.stats.ema:.3f}s — "
                                       f"checkpointing"))
                            if mgr is not None:
                                with save_lock:
                                    mgr.save(state, step + 1, async_=True)
                            monitor.consecutive = 0
                        elif (mgr is not None
                              and (step + 1) % rcfg.ckpt_every == 0):
                            with save_lock:
                                mgr.save(state, step + 1, async_=True)
        finally:
            if wd is not None:
                wd.close()
            saver.uninstall()
            if mgr is not None:
                with save_lock:
                    mgr.wait()
        end = start + len(losses)
        if mgr is not None and not preempted and end > start:
            with save_lock:
                mgr.save(state, end)
        return {"state": state, "losses": losses, "start_step": start,
                "end_step": end, "grid": grid_t, "preempted": preempted,
                "events": list(log.events)}

    return run
