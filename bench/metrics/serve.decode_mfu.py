"""serve.decode_mfu: a decode step's operations at the live lengths of
the active slots (bench/work/decoder.py, averaged over the window's steps)
over the device time per execution of the decode program in the traced
window times the bf16 peak."""

import trace_reduce
import work.decoder as wk


def read(rec):
    lay = rec.layer
    if rec.trace is None or not lay.get("decode_live"):
        return None
    n, dev_s = trace_reduce.module_runs(rec.trace, "jit__decode")
    if n == 0 or dev_s <= 0:
        return None
    cfg = lay["config"]
    steps = lay["decode_live"]
    flops = sum(wk.decode_step_flops(cfg, live) for live in steps) / len(steps)
    return 100.0 * flops / ((dev_s / n) * lay["peaks"]["bf16_flops_per_s"])
