"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes and
keeps a compact form: for each device, its operations (``XLA Ops`` line:
HLO instruction name, start, duration) and its program executions
(``XLA Modules`` line); and the host spans whose names start with
``bench.`` (the harness's own annotations, ``bench.window`` among them).
``save``/``read`` keep that form as gzipped JSON, which is what the tests
under ``bench/tests`` run on.

On a TPU an operation's event is named by its HLO instruction and carries
no category.  Collectives are known by their instruction names
(``all-reduce.3``, ``all-gather-start.1``, ...).  Convolutions are known
from the compiled program's HLO text (``conv_instructions``): XLA
convolutions, fusions that call one, and Pallas custom calls whose Mosaic
body is one of ``CONV_KERNELS``.

All times are nanoseconds on the trace's clock; every reduction below
takes the window from the ``bench.window`` span and clips device
intervals to it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Tuple

COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "send", "recv")
CONV_KERNELS = (b"_conv_kernel",)


# ----------------------------------------------------------------- load --

def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def conv_instructions(hlo_text: str) -> List[str]:
    """Names of the convolution instructions of a compiled program: the
    top-level instructions (those the device trace shows) that are a
    convolution, a fusion calling one, or a Pallas conv kernel."""
    import base64
    import re
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?(\S+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None and line.startswith(" "):
            comps[cur].append(line)
    called = {c for lines in comps.values() for ln in lines
              for c in re.findall(r"calls=%([^\s,]+)", ln)}
    conv_comps = {c for c, lines in comps.items()
                  if any(" convolution(" in ln for ln in lines)}
    out = set()
    for comp, lines in comps.items():
        if comp in called:
            continue
        for line in lines:
            m = re.match(r"^\s+(?:ROOT )?%(\S+) = ", line)
            if not m:
                continue
            calls = re.search(r"calls=%([^\s,]+)", line)
            body = re.search(r'"body":"([^"]+)"', line)
            if " convolution(" in line or (calls and calls.group(1)
                                            in conv_comps):
                out.add(m.group(1))
            elif body and "tpu_custom_call" in line:
                b64 = body.group(1)
                raw = base64.b64decode(b64 + "=" * (-len(b64) % 4))
                if any(k in raw for k in CONV_KERNELS):
                    out.add(m.group(1))
    return sorted(out)


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:") and "CPU" not in pname.upper():
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append([instruction(ev.name), int(ev.start_ns),
                                    int(ev.duration_ns)])
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
            if ops or mods:
                devices[pname] = {"ops": ops, "modules": mods}
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------ intervals --

def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(iv) -> int:
    return sum(e - s for s, e in iv)


def _minus(a, b) -> List[Tuple[int, int]]:
    """Union ``a`` minus union ``b`` (both sorted, disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _intersect(a, b) -> List[Tuple[int, int]]:
    """Union ``a`` intersected with union ``b`` (both sorted, disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def window(trace: dict) -> Tuple[int, int]:
    spans = [h for h in trace["host"] if h[0] == "bench.window"]
    if not spans:
        raise ValueError("trace has no bench.window span")
    _, s, d = spans[-1]
    return s, s + d


def _clip(ev_iv, win):
    s0, e0 = win
    return [(max(s, s0), min(e, e0)) for s, e in ev_iv
            if e > s0 and s < e0]


def is_collective(name: str) -> bool:
    return any(name.startswith(w) for w in COLLECTIVE_WORDS)


# ----------------------------------------------------------- reductions --

def device_summary(trace: dict) -> Dict[str, dict]:
    """Per device: busy, collective and exposed-collective seconds inside
    the window."""
    win = window(trace)
    out = {}
    for dev, d in trace["devices"].items():
        ops = d["ops"]
        allv = _union(_clip([(s, s + t) for _, s, t in ops], win))
        coll = _union(_clip([(s, s + t) for n, s, t in ops
                             if is_collective(n)], win))
        comp = _union(_clip([(s, s + t) for n, s, t in ops
                             if not is_collective(n)], win))
        out[dev] = {"busy_s": _length(allv) * 1e-9,
                    "collective_s": _length(coll) * 1e-9,
                    "exposed_collective_s": _length(_minus(coll, comp))
                    * 1e-9}
    return out


def window_s(trace: dict) -> float:
    s, e = window(trace)
    return (e - s) * 1e-9


def idle_share(trace: dict) -> Optional[float]:
    """Percent of the window in which no operation runs on a device,
    averaged over devices (None where the trace holds none)."""
    summ = device_summary(trace)
    win = window_s(trace)
    if not summ or win <= 0:
        return None
    busy = sum(d["busy_s"] for d in summ.values()) / len(summ)
    return 100.0 * (1.0 - busy / win)


def runs(trace: dict, prefix: str, device: str) -> List[Tuple[int, int]]:
    """Intervals of the executions of the programs whose name starts with
    ``prefix`` that lie wholly inside the window, on ``device``."""
    s0, e0 = window(trace)
    return sorted((s, s + t) for n, s, t in trace["devices"][device]["modules"]
                  if n.startswith(prefix) and s >= s0 and s + t <= e0)


def module_runs(trace: dict, prefix: str) -> Tuple[int, float]:
    """(executions, device seconds) of a program inside the window, on the
    first device."""
    if not trace["devices"]:
        return 0, 0.0
    iv = runs(trace, prefix, sorted(trace["devices"])[0])
    return len(iv), _length(iv) * 1e-9


def conv_in_runs(trace: dict, prefix: str, conv_ops) -> Tuple[int, float]:
    """(executions of the program on the first device, seconds of the
    ``conv_ops`` instructions inside each device's executions, summed over
    devices)."""
    conv_ops = set(conv_ops)
    devs = sorted(trace["devices"])
    if not devs:
        return 0, 0.0
    n = len(runs(trace, prefix, devs[0]))
    total = 0
    for dev in devs:
        iv = runs(trace, prefix, dev)
        conv = _union([(s, s + t) for name, s, t in
                       trace["devices"][dev]["ops"] if name in conv_ops])
        total += _length(_intersect(conv, iv))
    return n, total * 1e-9


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """The operations with most device time in the window (first device),
    by HLO instruction name."""
    if not trace["devices"]:
        return []
    win = window(trace)
    dev = sorted(trace["devices"])[0]
    tot: Dict[str, float] = {}
    for name, s, t in trace["devices"][dev]["ops"]:
        iv = _clip([(s, s + t)], win)
        if iv:
            tot[name] = tot.get(name, 0.0) + _length(iv) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """Idle device time in the window (first device), summed by what the
    host was doing: the ``bench.*`` span (other than ``bench.window``)
    that covers most of each gap, or ``host`` where none does."""
    if not trace["devices"]:
        return []
    win = window(trace)
    dev = sorted(trace["devices"])[0]
    busy = _union(_clip([(s, s + t) for _, s, t in
                         trace["devices"][dev]["ops"]], win))
    gaps = _minus([win], busy)
    spans = sorted((s, s + d, name) for name, s, d in trace["host"]
                   if name != "bench.window")
    tot: Dict[str, float] = {}
    for gs, ge in gaps:
        best, label = 0, "host"
        for s, e, name in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, name
        tot[label] = tot.get(label, 0.0) + (ge - gs) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]
