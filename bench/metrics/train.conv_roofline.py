"""train.conv_roofline: the least time of a step's convolutions (each
forward, dIn and dKer call bounded by the larger of its direct-algorithm
operations over the bf16 peak and its bytes over HBM bandwidth,
bench/work/cnn.py), times the train-step executions in the traced window,
over the device time of the convolution operations inside those
executions, summed over chips.  Which operations are convolutions is read
from the compiled step's HLO (``trace_reduce.conv_instructions``: XLA
convolutions, fusions that call one, the Pallas ``_conv_kernel``)."""

import trace_reduce


def read(rec):
    if rec.trace is None or not rec.layer.get("conv_ops"):
        return None
    n, conv_s = trace_reduce.conv_in_runs(rec.trace, "jit_train_step",
                                          rec.layer["conv_ops"])
    if n == 0 or conv_s <= 0:
        return None
    return 100.0 * n * rec.layer["conv_least_s"] / conv_s
