"""Benchmark harness — one module per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows and persists the perf
trajectory to ``BENCH_comm.json`` + ``BENCH_kernels.json`` +
``BENCH_serve.json`` at the repo root (schema per record: ``{name, grid,
schedule, wire_bytes, peak_elems, wall_ms}`` plus module-specific extras
— the serve records add ``{arch, tokens_per_s, p50_ms, p99_ms,
wire_bytes_per_tok}``).  Every record carries ``reps``/``std_ms``
(per-rep timing noise, warmup discarded) and a ``predicted_ms`` column —
the ``repro.perf`` trace-replay prediction under the alpha-beta
calibration fit from this same run, persisted as ``CALIB.json`` +
``CALIB_report.json``.  The JSON files are checked in
as the regression baseline: future PRs diff their wire/peak fields (exact
analytic/HLO quantities; ``wall_ms``/``measured_live_bytes`` are
machine-dependent and informational, and ``predicted_ms`` drift is gated
separately by the CI ``calib`` job).

  table12       Table 1/2 closed-form costs vs integer solver (the paper's
                central analytic result)
  comm          2D vs 2.5D vs 3D collective bytes + peak live memory across
                the allgather/ring/ring2 schedules, analytic vs HLO
                (Sec. 2.2 cost analysis)
  kernel        chip-level two-level tiling (Eq. 4 at VMEM scale)
  sharding      synthesizer-as-sharding-engine across the 10 assigned archs

``--quick`` is the CI smoke mode: fewer grids/layers/reps, skips the
sharding sweep, still writes both JSON files.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# runnable both as `python benchmarks/run.py` and `python -m benchmarks.run`
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: fewer grids/reps, skip the "
                         "sharding sweep")
    ap.add_argument("--out-dir", default=_ROOT,
                    help="where to write BENCH_*.json (default: repo root)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_comm_volume, bench_cost_model,
                            bench_kernels, bench_serve, bench_sharding)
    # comm/kernels print their rows from the JSON records below — no
    # second (CSV-only) benchmarking pass
    mods = [("cost_model", bench_cost_model)]
    if not args.quick:
        mods.append(("sharding", bench_sharding))

    print("name,us_per_call,derived")
    failed = 0
    for name, mod in mods:
        try:
            for row in mod.run():
                print(",".join(str(c) for c in row if str(c) != ""))
        except Exception:
            failed += 1
            print(f"{name},ERROR,", file=sys.stderr)
            traceback.print_exc()

    by_file = {}
    for fname, fn in [("BENCH_comm.json", bench_comm_volume.run_json),
                      ("BENCH_kernels.json", bench_kernels.run_json),
                      ("BENCH_serve.json", bench_serve.run_json)]:
        try:
            by_file[fname] = fn(quick=args.quick)
        except Exception:
            failed += 1
            print(f"{fname},ERROR,", file=sys.stderr)
            traceback.print_exc()

    # calibrate the alpha-beta cost model from this run's records, then
    # annotate every record with its replay prediction (predicted_ms next
    # to wall_ms) before persisting.  Fit failures are non-fatal: the
    # bench baselines are still written, just without predictions.
    try:
        from repro.perf.calibrate import (annotate_predictions,
                                          fit_collectives,
                                          prediction_error_report)
        fit_recs = (by_file.get("BENCH_comm.json", [])
                    + by_file.get("BENCH_serve.json", []))
        kern = by_file.get("BENCH_kernels.json", [])
        calib = fit_collectives(fit_recs, kernel_records=kern)
        calib.save(os.path.join(args.out_dir, "CALIB.json"))
        for recs in by_file.values():
            annotate_predictions(recs, calib)
        report = prediction_error_report(fit_recs + kern, calib)
        with open(os.path.join(args.out_dir, "CALIB_report.json"),
                  "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# calib median_rel_err="
              f"{report['summary']['median_rel_err']:.3f} over "
              f"{report['summary']['n_records']} records",
              file=sys.stderr)
    except Exception:
        failed += 1
        print("CALIB.json,ERROR,", file=sys.stderr)
        traceback.print_exc()

    for fname, recs in by_file.items():
        path = os.path.join(args.out_dir, fname)
        with open(path, "w") as f:
            json.dump(recs, f, indent=1, sort_keys=True)
            f.write("\n")
        for rec in recs:
            print(f"{rec['name']}/{rec['schedule']},"
                  f"{rec['wall_ms'] * 1e3:.0f},"
                  f"wire={rec['wire_bytes']:.3e}B,"
                  f"peak={rec['peak_elems']:.3e}el")
        print(f"# wrote {path} ({len(recs)} records)", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
