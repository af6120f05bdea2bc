"""Serving-engine benchmark: the continuous-batching engine
(`launch/serve.py`) on dense XLA vs the ``(Pm, Pn, Pc)`` serving grids,
on 8 virtual devices (subprocess; the bench process keeps 1 device).

Measures steady-state decode throughput and step-latency percentiles
(engines are warmed up so compilation never lands in the distribution)
and carries the analytic per-token wire / peak-memory accounting from
``repro.dist.lm``.  Every record carries ``tokens_match_dense`` — the
verified smoke grid ``(2,2,2)`` must match the dense engine's greedy
tokens (asserted); other grids record the bit (f32 rounding can flip a
near-tied argmax on a random-init smoke model, see docs/serving.md).

``run_json(quick=...)`` returns the ``BENCH_serve.json`` records
(schema: ``{arch, smoke, dtype, slots, grid, schedule, tokens_per_s,
p50_ms, p99_ms, wire_bytes_per_tok}`` + the common ``{name, wire_bytes,
peak_elems, wall_ms, std_ms, reps}`` baseline fields — enough to rebuild
the decode DAG for ``repro.perf`` prediction) that ``benchmarks/run.py``
persists.
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
os.environ["REPRO_DIST_PALLAS"] = "0"
import dataclasses
import json
import jax
from repro.configs import get_config
from repro.launch.serve import run

QUICK = %(quick)r
cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                          dtype="float32")
kw = dict(requests=4 if QUICK else 8,
          prompt_len=12, gen=8 if QUICK else 16,
          slots=4, warmup=True)

cells = [(None, "allgather")]          # dense baseline
cells += [((2, 2, 2), "allgather")]    # the smoke-pinned 2.5D grid
if not QUICK:
    cells += [((2, 2, 2), "ring2"),    # slab-memory schedule, same wire
              ((1, 4, 2), "allgather"),  # wire-optimal synthesized grid
              ((4, 2, 1), "allgather")]  # slots on m (2D-SUMMA)

out = []
dense_tokens = None
for grid, sched in cells:
    res = run(cfg, grid=grid, schedule=sched, **kw)
    if grid is None:
        dense_tokens = res["tokens"]
    gstr = "dense" if grid is None else "x".join(str(g) for g in grid)
    rec = {"name": f"serve/{cfg.arch_id}/{gstr}",
           "arch": cfg.arch_id,
           "smoke": True,
           "dtype": cfg.dtype,
           "slots": kw["slots"],
           "grid": list(grid) if grid else None,
           "schedule": sched,
           "tokens_per_s": res["tokens_per_s"],
           "p50_ms": res["p50_ms"],
           "p99_ms": res["p99_ms"],
           "wire_bytes_per_tok": res.get("wire_bytes_per_tok", 0.0),
           "wire_bytes": res.get("wire_bytes_per_tok", 0.0),
           "peak_elems": res.get("peak_mem_bytes", 0.0) / 4,
           "wall_ms": res["mean_ms"],
           "std_ms": res["std_ms"],
           "reps": res["reps"],
           "tokens_match_dense": (res["tokens"] == dense_tokens
                                  if grid is not None else True)}
    out.append(rec)
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
    payload = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("JSON")][0][4:]
    return json.loads(payload)


def run_json(*, quick: bool = False) -> list:
    """Records for ``BENCH_serve.json``."""
    recs = _collect(quick)
    assert all(r["tokens_match_dense"] for r in recs
               if r["grid"] == [2, 2, 2]), \
        [r["name"] for r in recs if not r["tokens_match_dense"]]
    return recs
