"""Best-of runtime autotuner for the local-kernel menu (PyDTNN's
``utils/best_of.py`` idiom, adapted to JAX trace-time dispatch).

``best_of(key, candidates, make_args)`` times every applicable candidate
implementation once per unique problem key — eagerly, on freshly drawn
concrete operands, while the surrounding computation is still tracing —
memoizes the winner, and persists the plan table to a JSON cache so the
distributed schedules pay the tuning cost once per machine:

* in-memory memo: one timing pass per key per process;
* on disk: ``.repro_autotune.json`` (override with ``REPRO_AUTOTUNE_CACHE``)
  — reloaded lazily, written atomically after each new measurement, and
  machine-specific (wall-clock winners), so it is *not* checked in.  Every
  key ends in the device kind (``kernels.ops.device_kind``), so a table
  timed on one device kind is never read on another;
* ``REPRO_AUTOTUNE`` env control: ``1`` (default) tunes, ``0`` disables
  the tuner entirely (callers fall back to their static paper-plan
  dispatch), ``refresh`` ignores persisted winners and re-times each key
  once this process.

:func:`autotune_disabled` is the in-process equivalent of
``REPRO_AUTOTUNE=0`` — ``repro.analysis`` wraps its HLO lowering in it so
the static verifier keeps proving the paper-plan schedules (and executes
nothing during what is otherwise a compile-only pass).

The actual candidate menus (direct Pallas conv, Winograd, im2col-GEMM,
XLA, ...) live in ``kernels.ops``; this module is policy-free timing and
persistence.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

MODE_ENV = "REPRO_AUTOTUNE"
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE = ".repro_autotune.json"
_SCHEMA_VERSION = 1

_disabled_depth = 0


def mode() -> str:
    """``"1"`` | ``"0"`` | ``"refresh"`` (unknown values read as "1")."""
    return os.environ.get(MODE_ENV, "1")


def enabled() -> bool:
    """True when the tuner may run (env not ``0``, no
    :func:`autotune_disabled` scope active)."""
    return mode() != "0" and _disabled_depth == 0


@contextlib.contextmanager
def autotune_disabled():
    """Force the static paper-plan dispatch within the scope (the
    in-process ``REPRO_AUTOTUNE=0``)."""
    global _disabled_depth
    _disabled_depth += 1
    try:
        yield
    finally:
        _disabled_depth -= 1


# --------------------------------------------------------------------------
# The persistable plan table
# --------------------------------------------------------------------------

class PlanCache:
    """Winner-per-key table with lazy JSON load and atomic save.

    Entries: ``{key: {"impl": name, "wall_ms": {candidate: ms},
    "refused": {candidate: error}}}`` (``refused`` only when a candidate
    failed)."""

    def __init__(self, path: Optional[str] = None):
        self._path_override = path
        self._mem: Dict[str, dict] = {}
        self._loaded_from: Optional[str] = None

    @property
    def path(self) -> str:
        return (self._path_override
                or os.environ.get(CACHE_ENV, DEFAULT_CACHE))

    def _load(self) -> None:
        path = self.path
        if self._loaded_from == path:
            return
        self._loaded_from = path
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
            plans = data.get("plans", {}) \
                if isinstance(data, dict) else {}
            for key, ent in plans.items():
                self._mem.setdefault(key, ent)
        except (OSError, ValueError):
            pass  # missing/corrupt cache: re-time

    def lookup(self, key: str, *, allow_file: bool = True) -> Optional[dict]:
        if key in self._mem:
            return self._mem[key]
        if allow_file:
            self._load()
        return self._mem.get(key)

    def record(self, key: str, impl: str, wall_ms: Dict[str, float],
               refused: Optional[Dict[str, str]] = None) -> None:
        self._mem[key] = {"impl": impl, "wall_ms": wall_ms}
        if refused:
            self._mem[key]["refused"] = dict(refused)
        self.save()

    def save(self) -> None:
        """Atomic best-effort write (a read-only FS must not break
        dispatch)."""
        path = self.path
        payload = {"version": _SCHEMA_VERSION, "plans": self._mem}
        try:
            d = os.path.dirname(os.path.abspath(path))
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass

    def reset(self) -> None:
        self._mem.clear()
        self._loaded_from = None


_cache = PlanCache()


def plan_cache() -> PlanCache:
    """The process-wide plan table."""
    return _cache


# --------------------------------------------------------------------------
# Timing
# --------------------------------------------------------------------------

def _time_ms(fn: Callable, args: tuple, *, reps: int
             ) -> Tuple[float, Optional[str]]:
    """``(ms, None)``: min-of-``reps`` wall ms of the jitted candidate (one
    warmup call pages everything in); ``(inf, error)`` when the candidate
    fails to compile or run.

    Compiled ahead of time (``jit(fn).lower(...).compile()``): dispatch
    happens at trace time, so a timing pass is often reached while an
    outer ``jax.jit`` trace is live — a plain inner ``jit`` call would
    be staged into the outer jaxpr (returning tracers), while the AOT
    executable runs concretely in any context."""
    try:
        jfn = jax.jit(fn).lower(*args).compile()
        jfn(*args).block_until_ready()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jfn(*args).block_until_ready()
            best = min(best, (time.perf_counter() - t0) * 1e3)
        return best, None
    except Exception as e:  # noqa: BLE001 — recorded, never silent
        msg = str(e).strip().splitlines()
        return float("inf"), f"{type(e).__name__}: {msg[0] if msg else ''}"


def best_of(key: str, candidates: Sequence[Tuple[str, Callable]],
            make_args: Callable[[], tuple], *, reps: int = 2) -> str:
    """Winning implementation name for ``key``.

    ``candidates`` is an ordered ``(name, fn)`` menu (first entry wins
    ties); ``make_args`` draws the concrete operands the timing pass runs
    on.  The winner is memoized in the process-wide :class:`PlanCache`
    and persisted; a candidate that failed is kept in the entry's
    ``refused`` map with its error.  When every candidate fails, the CPU
    falls back to the first (interpret-mode kernels under an outer trace
    can fail to time); any other backend raises."""
    names = [n for n, _ in candidates]
    if len(names) == 1:
        return names[0]
    ent = _cache.lookup(key, allow_file=mode() != "refresh")
    if ent and ent.get("impl") in names:
        return ent["impl"]
    # dispatch runs while an outer jit traces; without this scope the
    # operands drawn here would be that trace's tracers, and no candidate
    # could run on them
    with jax.ensure_compile_time_eval():
        args = make_args()
    timed = {name: _time_ms(fn, args, reps=reps) for name, fn in candidates}
    wall_ms = {name: t for name, (t, _) in timed.items()}
    refused = {name: err for name, (_, err) in timed.items() if err}
    if len(refused) == len(names):
        if jax.default_backend() != "cpu":
            raise RuntimeError(f"every candidate for {key} failed: {refused}")
        # leave the key untuned for a later pass
        return names[0]
    impl = min(names, key=lambda n: wall_ms[n])  # first-listed wins ties
    _cache.record(key, impl, wall_ms, refused)
    return impl


# --------------------------------------------------------------------------
# CLI: warm the plan table for the canonical workload
# --------------------------------------------------------------------------

def warm(*, batch: int = 4, refresh: bool = False,
         layers: Optional[List[str]] = None) -> Dict[str, dict]:
    """Autotune the ResNet-50 layer table (each conv at its real stride,
    SAME padding, benchmark batch) plus the classifier-head matmul
    shapes, returning ``{layer: {"impl": ..., "wall_ms": ...}}``.  This
    is ``make autotune`` — run once per machine so every later process
    (dist schedules, benches, CI) starts from a hot plan table."""
    import jax.numpy as jnp

    from repro.core.problem import resnet50_layers
    from repro.kernels import autotune as _canonical
    from repro.kernels import ops as kops

    # under ``python -m repro.kernels.autotune`` this module is loaded
    # twice (__main__ and the canonical import kops dispatches through);
    # read the plan table best_of actually records into
    cache = _canonical.plan_cache()
    if refresh:
        os.environ[MODE_ENV] = "refresh"
    table: Dict[str, dict] = {}
    key0 = jax.random.PRNGKey(0)
    items = resnet50_layers(batch=batch).items()
    if layers is not None:
        items = [(n, p) for n, p in items if n in layers]
    for name, p in items:
        stride = (p.sh, p.sw)
        # SAME-conv input extents that land on the table's output dims
        x = jax.random.normal(
            key0, (p.Nb, p.Nc, p.sh * p.Nh, p.sw * p.Nw), jnp.float32)
        w = jax.random.normal(key0, (p.Nk, p.Nc, p.Nr, p.Ns), jnp.float32)
        impl = kops.select_conv_impl(x.shape, w.shape, x.dtype,
                                     stride, "SAME")
        ent = cache.lookup(kops.conv_key(x.shape, w.shape, x.dtype,
                                         stride, "SAME"))
        table[name] = {"impl": impl,
                       "wall_ms": (ent or {}).get("wall_ms", {})}
    # classifier-head style matmuls
    for m, c, n in [(batch, 512, 1000), (256, 256, 256)]:
        impl = kops.select_matmul_impl(m, n, c, jnp.float32)
        table[f"matmul_{m}x{c}x{n}"] = {"impl": impl}
    return table


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="warm the local-kernel autotune plan cache")
    ap.add_argument("--refresh", action="store_true",
                    help="re-time every key, ignoring persisted winners")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)
    table = warm(batch=args.batch, refresh=args.refresh)
    for name, ent in table.items():
        times = ent.get("wall_ms") or {}
        detail = " ".join(f"{k}={v:.2f}ms" for k, v in sorted(times.items())
                          if v != float("inf"))
        print(f"{name}: {ent['impl']}" + (f"  [{detail}]" if detail else ""))
    print(f"# plan table: {plan_cache().path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
