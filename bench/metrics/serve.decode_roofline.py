"""serve.decode_roofline: the least time of a decode step -- the larger of
its operations over the bf16 peak and the bytes it needs over HBM
bandwidth (bench/work/decoder.py, at the live lengths of the active slots,
averaged over the window's steps) -- over the device time per execution of
the engine's decode program in the traced window."""

import trace_reduce
import work.decoder as wk


def read(rec):
    lay = rec.layer
    if rec.trace is None or not lay.get("decode_live"):
        return None
    n, dev_s = trace_reduce.module_runs(rec.trace, "jit__decode")
    if n == 0 or dev_s <= 0:
        return None
    cfg, pk = lay["config"], lay["peaks"]
    steps = lay["decode_live"]
    least = sum(max(wk.decode_step_flops(cfg, live) / pk["bf16_flops_per_s"],
                    wk.decode_step_bytes(cfg, live) / pk["hbm_bytes_per_s"])
                for live in steps) / len(steps)
    return 100.0 * least / (dev_s / n)
