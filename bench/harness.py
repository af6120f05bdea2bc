"""What every cell's run shares: finding the cell's files by name, the
device check, the compile cache, tracing, memory, and the result line.

A run is one call of a driver (``bench/drivers/<driver>.py``, named by the
configuration file), which returns a record: the end-to-end metrics it
timed, what the per-layer readers need, and the numbers its comparison
with the plain reference produced.  The per-layer metrics are computed
from that record by the readers in ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def find_cell(bm: dict, workload: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_module(*parts):
    """Import ``bench/<parts>.py`` by path (names may hold '-' or '.')."""
    path = os.path.join(BENCH, *parts) + ".py"
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prepare_process() -> str:
    """Environment every run sets before JAX loads: the program on the
    path, the static kernel plan, and the compile cache inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` already points)."""
    for p in (os.path.join(ROOT, "src"), BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    # The autotuner times each candidate once, 2 reps: its winners would
    # follow noise and put candidate compiles into set-up.
    os.environ["REPRO_AUTOTUNE"] = "0"
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    return cache


def enable_cache(cache: str) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_LOWERED: list = []


def count_compiles() -> None:
    """Record the programs JAX lowers from here on (each new program, or
    a new shape of one, is lowered once before it compiles or is read
    from the cache)."""
    import jax.monitoring

    def on_event(name, *_a, fun_name="", **_k):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            _LOWERED.append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(on_event)


def compiles() -> int:
    return len(_LOWERED)


def lowered_between(a: int, b: int) -> list:
    """Names of the programs lowered between two ``compiles()`` counts."""
    return _LOWERED[a:b]


def chips(n: int):
    """The first ``n`` accelerator devices; ``NoChip`` where JAX sees none,
    or fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"JAX finds no accelerator (platform "
                     f"{devs[0].platform!r}, {len(devs)} device(s))")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device, as its allocator counts
    them (0 where a backend keeps no statistics).  A compiled program's
    temporaries may not be among them: drivers take the larger of this and
    ``program_bytes`` of the timed program."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def program_bytes(compiled) -> int:
    """Device bytes a compiled program holds while it runs: arguments,
    outputs and temporaries, less what outputs alias (0 where the backend
    gives no analysis)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return 0
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


# -------------------------------------------------------------- tracing --

class Tracer:
    """Profiler trace of the window (``--trace 1``) and the harness's
    own host spans in it; with ``--trace 0`` every call is a no-op."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.trace = None
        self._window = None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.on:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self) -> None:
        if not self.on or self._window is None:
            return
        import jax

        import trace_reduce
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        try:
            self.trace = trace_reduce.load(trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------- record --

@dataclass
class Run:
    """What a driver is given."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    tracer: Tracer
    devices: list
    peaks: dict
    t_start: float                      # process start, monotonic
    fault: Optional[Callable] = None    # tests: break the timed path


@dataclass
class Record:
    """What a driver returns."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, float]            # numbers compared, by name
    memory_peak_bytes: int
    layer: Dict[str, object] = field(default_factory=dict)
    trace: Optional[dict] = None
    window_s: float = 0.0
    window_programs: list = field(default_factory=list)  # lowered in it


def limits(workload: str) -> Dict[str, float]:
    path = os.path.join(BENCH, "limits", workload + ".json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)["limits"]


def judge(checks: Dict[str, float], lim: Dict[str, float]) -> dict:
    """Each number beside its limit; a number passes at or below it."""
    out = {}
    for name, value in checks.items():
        limit = lim.get(name)
        ok = (limit is not None and value == value and value <= limit)
        out[name] = {"value": value, "limit": limit, "ok": ok}
    return out
