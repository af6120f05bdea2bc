"""The paper's central comparison (Sec. 2.2 cost analysis): communication
volume and peak live memory of the 2D / 2.5D / 3D distributed CNN
algorithms — analytic accounting vs collective wire bytes and per-device
live bytes measured from compiled HLO on 8 virtual devices (subprocess;
the bench process keeps 1 device).  Covers all three schedules
(``allgather`` / ``ring`` / ``ring2``) for the forward pass and the
fwd+bwd train step through the dist-op custom VJPs.

``run_json(quick=...)`` returns the ``BENCH_comm.json`` records (schema:
``{name, grid, schedule, wire_bytes, peak_elems, wall_ms}``) that
``benchmarks/run.py`` persists as the regression baseline and also prints
as CSV rows.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import time
import jax, jax.numpy as jnp
from repro.dist.conv2d import (conv2d_distributed, conv_mem_elems,
                               conv_train_comm_elems, conv_train_mem_elems,
                               make_conv_mesh)
from repro.launch.hlo_analysis import analyze_hlo, live_bytes

QUICK = %(quick)r
# c-heavy shape: the contraction-operand memory the 2.5D/3D family (and
# the ring2 schedule) exists to manage dominates the conv scratch
N, C, H, W, K, kh = 8, 128, 8, 8, 32, 3
xs = jax.ShapeDtypeStruct((N, C, H, W), jnp.float32)
ws = jax.ShapeDtypeStruct((K, C, kh, kh), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(0), (N, C, H, W), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (K, C, kh, kh), jnp.float32)

grids = [((8,1,1,1,1), "2D-DP"), ((2,1,1,2,2), "2.5D")]
if not QUICK:
    grids += [((4,1,1,2,1), "2D-SUMMA"), ((1,1,1,2,4), "3D-ish")]
reps = 3 if QUICK else 5

def wall_ms(compiled_fn, *args):
    # takes the already-compiled executable: no recompile for timing.
    # The warmup rep is discarded and each rep is timed individually so
    # the record carries a noise estimate (std_ms) next to the mean —
    # the CI calib gate tolerates drift below the timing noise.
    jax.block_until_ready(compiled_fn(*args))   # warmup (discarded)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled_fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    mean = sum(times) / reps
    std = (sum((t - mean) ** 2 for t in times) / reps) ** 0.5
    return {"wall_ms": mean, "std_ms": std, "reps": reps}

shapes = {"x_shape": [N, C, H, W], "w_shape": [K, C, kh, kh]}
out = []
for grid, algo in grids:
    mesh = make_conv_mesh(grid)
    for sched in ["allgather", "ring", "ring2"]:
        fn = jax.jit(lambda a, b, s=sched: conv2d_distributed(
            a, b, mesh, schedule=s))
        compiled = fn.lower(xs, ws).compile()
        rep = analyze_hlo(compiled.as_text())
        live = live_bytes(compiled)
        mem = conv_mem_elems((N,C,H,W), (K,C,kh,kh), grid, schedule=sched)
        out.append({"name": f"comm/fwd/{algo}", "grid": list(grid),
                    "schedule": sched,
                    "wire_bytes": rep["total_wire_bytes"],
                    "peak_elems": mem["peak"],
                    "measured_live_bytes": live,
                    **shapes, **wall_ms(compiled, x, w)})
        def fwd_bwd(a, b, s=sched):
            y, vjp = jax.vjp(lambda p, q: conv2d_distributed(
                p, q, mesh, schedule=s), a, b)
            return vjp(y)
        cb = jax.jit(fwd_bwd).lower(xs, ws).compile()
        repb = analyze_hlo(cb.as_text())
        liveb = live_bytes(cb)
        memb = conv_train_mem_elems((N,C,H,W), (K,C,kh,kh), grid,
                                    schedule=sched)
        analytic = conv_train_comm_elems((N,C,H,W), (K,C,kh,kh), grid,
                                         schedule=sched)["total"] * 4
        out.append({"name": f"comm/train/{algo}", "grid": list(grid),
                    "schedule": sched,
                    "wire_bytes": repb["total_wire_bytes"],
                    "analytic_wire_bytes": analytic,
                    "peak_elems": memb["peak"],
                    "measured_live_bytes": liveb,
                    **shapes, **wall_ms(cb, x, w)})
    # the memory-for-wire endpoint: residual-saving VJP, allgather sched
    def fwd_bwd_sg(a, b):
        y, vjp = jax.vjp(lambda p, q: conv2d_distributed(
            p, q, mesh, save_gathered=True), a, b)
        return vjp(y)
    cs = jax.jit(fwd_bwd_sg).lower(xs, ws).compile()
    reps_ = analyze_hlo(cs.as_text())
    out.append({"name": f"comm/train-save-gathered/{algo}",
                "grid": list(grid), "schedule": "allgather",
                "wire_bytes": reps_["total_wire_bytes"],
                "analytic_wire_bytes": conv_train_comm_elems(
                    (N,C,H,W), (K,C,kh,kh), grid,
                    save_gathered=True)["total"] * 4,
                "peak_elems": conv_train_mem_elems(
                    (N,C,H,W), (K,C,kh,kh), grid,
                    save_gathered=True)["peak"],
                "measured_live_bytes": live_bytes(cs),
                **shapes, **wall_ms(cs, x, w)})
print("JSON" + json.dumps(out))
"""


def _collect(quick: bool) -> list:
    # the child measures virtual CPU devices by design: pin it to the CPU
    # so it never reaches for an accelerator the parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_ROOT, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    body = textwrap.dedent(_BODY % {"quick": quick})
    proc = subprocess.run([sys.executable, "-c", body],
                          env=env, capture_output=True, text=True,
                          timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = [l for l in proc.stdout.splitlines()
               if l.startswith("JSON")][0][4:]
    return json.loads(payload)


def run_json(*, quick: bool = False) -> list:
    """Records for ``BENCH_comm.json``."""
    return _collect(quick)
