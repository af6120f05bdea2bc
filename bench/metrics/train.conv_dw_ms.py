"""train.conv_dw_ms: device time per step and chip of the instructions of
the compiled train step whose ``op_name`` holds the ``conv.dw`` scope (the
weight-gradient correlations of ``dist/conv2d.py _dw_local`` and their
transposes), inside the step executions of the traced window.  None where
the record holds no scoped instructions (``bench/spans.py``)."""

import trace_reduce


def read(rec):
    ops = rec.layer.get("scoped_ops", {}).get("conv.dw")
    if rec.trace is None or not ops:
        return None
    n, dw_s = trace_reduce.conv_in_runs(rec.trace, "jit_train_step", ops)
    if n == 0 or dw_s <= 0:
        return None
    return 1e3 * dw_s / (n * rec.layer["chips"])
