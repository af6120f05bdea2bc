"""train.device_idle_share: 1 - the union of device operation intervals
over the traced window, averaged over chips (``trace_reduce.idle_share``)."""

import trace_reduce


def read(rec):
    return None if rec.trace is None else trace_reduce.idle_share(rec.trace)
