#!/usr/bin/env python3
"""The program's own spans and scopes in a traced run, and their readers.

The serving engine and the train loop record their phases as
``jax.profiler`` annotations named ``serve.*`` and ``train.*``
(``src/repro/launch/serve.py``, ``src/repro/dist/train.py``), some with
stats (``rid``, ``bucket``, ``queued_ms``; ``step``, ``active``,
``queued``; ``step_num``).  The distributed convolution's local
contractions run under the named scopes ``conv.fwd``, ``conv.dx`` and
``conv.dw`` (``src/repro/dist/conv2d.py``), which the compiled program
keeps in each instruction's ``op_name``.

* ``host_spans`` reads those annotations from an ``.xplane.pb``, in the
  form ``[name, start_ns, duration_ns, stats]``; a reduced trace holds
  them under ``spans``.
* ``scoped_instructions`` names the top-level instructions of compiled HLO
  text whose ``op_name`` holds a scope; a training record holds them under
  ``layer["scoped_ops"]``, by scope.
* ``idle_by_span`` splits the window's idle device time by the innermost
  host span (program or harness) open at each instant.

The readers ``bench/metrics/serve.queue_wait_p95_ms.py``,
``serve.admit_ms.py``, ``serve.decode_read_ms.py``,
``serve.decode_bookkeep_ms.py``, ``train.input_ms.py`` and
``train.conv_dw_ms.py`` read a record that holds them, and return None
where it does not.  ``trace_reduce.load`` keeps only the ``bench.*`` host
spans, and the training driver keeps no scoped instructions, so a
``run_cell.py`` record holds neither.  This command runs one traced cell
with both and prints its result line, to which it adds the readers'
values (``spans``), ``idle_by_span`` and the engine's ``decode_counters``:

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

``KEEP_TRACE_TO`` keeps the reduced trace with its spans, as for
``run_cell.py``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

PREFIXES = ("serve.", "train.")
SCOPES = ("conv.fwd", "conv.dx", "conv.dw")
READERS = ("serve.queue_wait_p95_ms", "serve.admit_ms",
           "serve.decode_read_ms", "serve.decode_bookkeep_ms",
           "train.input_ms", "train.conv_dw_ms")


# ----------------------------------------------------------------- read --

def host_spans(path: str) -> List[list]:
    """The host events named ``serve.*`` / ``train.*`` of an
    ``.xplane.pb``: ``[name, start_ns, duration_ns, stats]``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append([ev.name, int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)])
    return out


def scoped_instructions(hlo_text: str, scope: str) -> List[str]:
    """Names of the top-level instructions of a compiled program (those a
    device trace shows: not inside a fusion's or a reduction's called
    computation) whose ``op_name`` holds ``scope`` as one level of its
    name stack, bare or wrapped by a transformation
    (``.../conv.dw/...``, ``.../transpose(jvp(conv.dw))/...``)."""
    level = re.compile(r'op_name="(?:[^"]*[/(])?' + re.escape(scope)
                       + r'[/)"]')
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?(\S+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None and line.startswith(" "):
            comps[cur].append(line)
    called = {c for lines in comps.values() for ln in lines
              for c in re.findall(r"(?:calls|to_apply)=%([^\s,]+)", ln)}
    out = set()
    for comp, lines in comps.items():
        if comp in called:
            continue
        for line in lines:
            m = re.match(r"^\s+(?:ROOT )?%(\S+) = ", line)
            if m and level.search(line):
                out.add(m.group(1))
    return sorted(out)


# ----------------------------------------------------------- reductions --

def in_window(trace: dict, name: str) -> Optional[List[list]]:
    """The spans named ``name`` that lie wholly inside ``bench.window``;
    None where the trace holds no program spans."""
    if trace is None or "spans" not in trace:
        return None
    s0, e0 = trace_reduce.window(trace)
    return [sp for sp in trace["spans"]
            if sp[0] == name and sp[1] >= s0 and sp[1] + sp[2] <= e0]


def median_ms(trace: dict, name: str) -> Optional[float]:
    """Median duration of the window's spans named ``name``, in ms."""
    found = in_window(trace, name)
    if not found:
        return None
    return statistics.median(sp[2] for sp in found) * 1e-6


def decode_counters(trace: dict) -> Dict[str, list]:
    """The engine's ``active`` (occupied slots) and ``queued`` (queue
    depth) counters over the window's decode steps: [min, median, max]
    each; empty where the window holds no ``serve.decode`` span."""
    steps = in_window(trace, "serve.decode")
    if not steps:
        return {}
    out = {}
    for key in ("active", "queued"):
        vals = sorted(sp[3][key] for sp in steps)
        out[key] = [vals[0], statistics.median(vals), vals[-1]]
    return out


def idle_by_span(trace: dict, n: int = 10) -> List[list]:
    """Idle device time in the window (first device), by the innermost
    host span open at each instant: program spans and the harness's
    ``bench.*`` spans (``bench.window`` aside), ``host`` where none is
    open.  Seconds, most first."""
    if not trace["devices"]:
        return []
    win = trace_reduce.window(trace)
    dev = sorted(trace["devices"])[0]
    busy = trace_reduce._union(trace_reduce._clip(
        [(s, s + t) for _, s, t in trace["devices"][dev]["ops"]], win))
    gaps = trace_reduce._minus([win], busy)
    spans = [(name, s, s + d, 0) for name, s, d in trace["host"]
             if name != "bench.window"]
    spans += [(sp[0], sp[1], sp[1] + sp[2], 1)
              for sp in trace.get("spans", [])]
    # labelled segments: between two span edges, the innermost open span
    # (the latest start; of equal starts, the earliest end; of equal
    # intervals, the program's span, which the harness's encloses)
    edges = sorted({t for sp in spans for t in sp[1:3]} | set(win))
    by_start = sorted(spans, key=lambda sp: sp[1])
    segs, open_, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        label = (max(open_, key=lambda sp: (sp[1], -sp[2], sp[3]))[0]
                 if open_ else "host")
        segs.append((a, b, label))
    tot: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        i = j
        while i < len(segs) and segs[i][0] < ge:
            ov = min(ge, segs[i][1]) - max(gs, segs[i][0])
            if ov > 0:
                tot[segs[i][2]] = tot.get(segs[i][2], 0.0) + ov * 1e-9
            i += 1
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


# ------------------------------------------------------------------ run --

def traced_run(workload: str, seed: int, seconds: float, devices=None):
    """One ``--trace 1`` run of ``workload`` with the program's spans in
    its trace (``trace_reduce.load`` wrapped to add them) and, for
    training, the scoped instructions of the compiled step (read from the
    HLO text the driver hands to ``trace_reduce.conv_instructions``).
    Returns (record, result) with ``spans``, ``idle_by_span`` and
    ``decode_counters`` added to the result."""
    import run_cell
    load, conv_instructions = trace_reduce.load, trace_reduce.conv_instructions
    hlo = []

    def load_with_spans(path):
        trace = load(path)
        t0 = time.monotonic()
        trace["spans"] = host_spans(path)
        print(f"program spans: {len(trace['spans'])} read in "
              f"{time.monotonic() - t0:.1f}s", file=sys.stderr)
        return trace

    def keep_hlo(text):
        hlo.append(text)
        return conv_instructions(text)

    trace_reduce.load = load_with_spans
    trace_reduce.conv_instructions = keep_hlo
    try:
        rec, result = run_cell.execute(workload, seed, seconds, True,
                                       devices=devices)
    finally:
        trace_reduce.load = load
        trace_reduce.conv_instructions = conv_instructions
    if hlo:
        rec.layer["scoped_ops"] = {s: scoped_instructions(hlo[-1], s)
                                   for s in SCOPES}
    values = {}
    for name in READERS:
        v = harness.load_module("metrics", name).read(rec)
        if v is not None:
            values[name] = v
    result["spans"] = values
    if rec.trace is not None:
        result["idle_by_span"] = idle_by_span(rec.trace)
        result["decode_counters"] = decode_counters(rec.trace)
    return rec, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cache = harness.prepare_process()
    import run_cell
    run_cell.T_START = T_START
    harness.enable_cache(cache)
    harness.count_compiles()
    try:
        _, result = traced_run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 1
    print(f"run took {time.monotonic() - T_START:.1f}s", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
