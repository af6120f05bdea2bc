#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, and the serving knee.

    python3 bench/readings.py readings --workload <cell> --seeds 1,2,3 \
        [--seconds S] [--control] [--faults]
    python3 bench/readings.py sweep --workload <cell> --rates 1,2,3 \
        --seconds S
    python3 bench/readings.py control --workload <cell> \
        --checked kept1.json,kept2.json

``readings`` runs the cell's timed path once per seed, in one process
(the program's numbers: the lower reading), and with ``--control`` for a
training cell the control on the same seed: the plain reference put in
the program's place at the precision below the configuration's (``high``
for f32 at ``highest``).  With ``--faults`` a training cell also reads
the reference fed half of each batch.  Each reading is one JSON line on
standard output.

``sweep`` serves the cell's mix at each offered rate in turn on one
engine and prints what was completed: the knee is the highest rate that
holds.  ``control`` reads the serving control (float8 e4m3 matmul
operands for bf16) on the prompts and served tokens that runs of
``run_cell.py`` kept (``KEEP_CHECKED_TO``).

The benchmark's own runs never run this; it needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

CONTROL = {"highest": "high", "bfloat16": "fp8", "float32": "bf16"}


def _run(workload, seed, seconds):
    import run_cell
    return run_cell.execute(workload, seed, seconds, False)


def readings(args) -> None:
    bm = harness.benchmark()
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    for seed in args.seeds:
        t = time.monotonic()
        rec, res = _run(args.workload, seed, args.seconds)
        print(json.dumps({"kind": "program", "seed": seed,
                          "checks": rec.checks, "correct": res["correct"],
                          "metrics": res["metrics"],
                          "detail": rec.layer.get("detail"),
                          "s": time.monotonic() - t}), flush=True)
        if cfg["driver"] == "train_cnn":
            import drivers_train as drv
            ref = drv.reference_run(cfg, mix, seed, cfg["matmul_precision"])
            if args.control:
                ctl = drv.reference_run(cfg, mix, seed,
                                        CONTROL[cfg["matmul_precision"]])
                print(json.dumps({"kind": "control", "seed": seed,
                                  "checks": drv.compare(ctl, ref),
                                  "detail": drv.detail(ctl, ref)}),
                      flush=True)
            if args.faults:
                half = drv.reference_run(cfg, mix, seed,
                                         cfg["matmul_precision"],
                                         half_batch=True)
                print(json.dumps({"kind": "fault_half_batch", "seed": seed,
                                  "checks": drv.compare(half, ref)}),
                      flush=True)


def sweep(args) -> None:
    import drivers_serve as drv
    bm = harness.benchmark()
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    harness.chips(cell["chips"])
    for row in drv.sweep(cfg, mix, args.seeds[0], args.seconds, args.rates):
        print(json.dumps(row), flush=True)


def control(args) -> None:
    """The serving control on the prompts and served tokens that runs of
    ``run_cell.py`` kept (``KEEP_CHECKED_TO``), one file per seed."""
    import drivers_serve as drv
    bm = harness.benchmark()
    cell = harness.find_cell(bm, args.workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    harness.chips(cell["chips"])
    for path in args.checked:
        with open(path, encoding="utf-8") as f:
            kept = json.load(f)
        make, key = drv.make_params(cfg, kept["seed"])
        params = make(key)
        gaps = drv.reference_gaps(cfg, params, kept["checked"],
                                  mix["engine"]["max_seq"],
                                  quant=CONTROL[cfg["dtype"]])
        print(json.dumps({"kind": "control", "seed": kept["seed"],
                          "checks": {"token_gap": drv.widest(gaps)},
                          "tokens": int(sum(len(g) for g in gaps))}),
              flush=True)
        del params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="",
                    type=lambda s: [float(x) for x in s.split(",") if x])
    ap.add_argument("--checked", default="",
                    type=lambda s: [x for x in s.split(",") if x])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    cache = harness.prepare_process()
    import jax  # noqa: F401
    harness.enable_cache(cache)
    sys.modules["drivers_train"] = harness.load_module("drivers",
                                                       "train_cnn")
    sys.modules["drivers_serve"] = harness.load_module("drivers", "serve_lm")
    {"readings": readings, "sweep": sweep, "control": control}[args.mode](
        args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
