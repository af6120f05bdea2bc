"""train.collective_exposed_share: per chip, the time in which a
collective operation runs and no other operation does, over that chip's
busy time in the traced window; the worst chip.  Only where the cell runs
on more than one chip."""

import trace_reduce


def read(rec):
    if rec.trace is None or rec.layer.get("chips", 1) < 2:
        return None
    summ = trace_reduce.device_summary(rec.trace)
    shares = [d["exposed_collective_s"] / d["busy_s"] for d in summ.values()
              if d["busy_s"] > 0]
    return 100.0 * max(shares) if shares else None
