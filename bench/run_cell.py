#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``); the configuration names the driver
(``bench/drivers/<driver>.py``).  With ``--trace 0`` the result's metrics
are the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, each from its reader ``bench/metrics/<metric>.py``.

The run fails (exit 1, no result line) where JAX finds no accelerator or
fewer chips than the cell asks for.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the plain reference beside its limit, which the last
lines of standard error repeat.  ``KEEP_TRACE_TO=<file.json.gz>`` keeps a
``--trace 1`` run's reduced trace (how ``bench/tests/data`` was recorded);
``KEEP_CHECKED_TO=<file.json>`` a serving run's compared prompts and
served tokens (what ``readings.py control`` reads).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def per_layer(bm: dict, workload: str, rec) -> dict:
    out = {}
    for m in bm["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = harness.load_module("metrics", m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(bm: dict, workload: str, rec) -> dict:
    out = {}
    for m in bm["end_to_end"]:
        if workload not in m.get("workloads", [workload]):
            continue
        if m["name"] in rec.end_to_end:
            out[m["name"]] = {"value": rec.end_to_end[m["name"]],
                              "unit": m["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool,
            fault=None, devices=None):
    """Drive one run of ``workload``; return (record, result dict).
    ``devices`` skips the look for a chip (tests on the CPU)."""
    bm = harness.benchmark()
    cell = harness.find_cell(bm, workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    if devices is None:
        devices = harness.chips(cell["chips"])
    kind = devices[0].device_kind
    # on the CPU (the tests) the records are formed with the v5e's peaks;
    # no CPU run reports a device metric
    peaks = harness.peaks_for(kind if devices[0].platform != "cpu"
                              else "TPU v5 lite")
    driver = harness.load_module("drivers", cfg["driver"])
    run = harness.Run(cell=cell, config=cfg, mix=mix, seed=seed,
                      seconds=seconds, tracer=harness.Tracer(trace),
                      devices=devices, peaks=peaks, t_start=T_START,
                      fault=fault)
    rec = driver.run(run)
    if rec.window_programs:
        print(f"compiled in the window: {rec.window_programs}",
              file=sys.stderr)
    judged = harness.judge(rec.checks, harness.limits(workload))
    correct = all(j["ok"] for j in judged.values()) and bool(judged)
    result = {
        "correct": correct,
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": (per_layer(bm, workload, rec) if trace
                    else end_to_end(bm, workload, rec)),
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": rec.memory_peak_bytes},
    }
    if trace and rec.trace is not None:
        import trace_reduce
        if os.environ.get("KEEP_TRACE_TO"):
            trace_reduce.save(dict(rec.trace,
                                   conv_ops=rec.layer.get("conv_ops", [])),
                              os.environ["KEEP_TRACE_TO"])
        summ = trace_reduce.device_summary(rec.trace)
        result["device"]["busy_s"] = (sum(d["busy_s"] for d in summ.values())
                                      / max(len(summ), 1))
        result["device"]["window_s"] = trace_reduce.window_s(rec.trace)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(rec.trace),
            "idle_gaps": trace_reduce.idle_gaps(rec.trace)}
    if os.environ.get("KEEP_CHECKED_TO") and "checked" in rec.layer:
        with open(os.environ["KEEP_CHECKED_TO"], "w",
                  encoding="utf-8") as f:
            json.dump({"seed": seed, "checked": rec.layer["checked"]}, f)
    result["window_compiles"] = len(rec.window_programs)
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in judged.items()}
    return rec, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cache = harness.prepare_process()
    try:
        import jax  # noqa: F401
    except ImportError as e:
        print(f"run_cell: cannot import jax ({e})", file=sys.stderr)
        return 1
    harness.enable_cache(cache)
    harness.count_compiles()
    try:
        _, result = execute(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    except harness.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 1
    print(f"programs compiled in the window: {result['window_compiles']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
