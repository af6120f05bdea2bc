"""train.mfu: model operations per step (bench/work/cnn.py) times the
steps completed per second of the traced run's window, over the chips'
bf16 peak (bench/peaks.json)."""


def read(rec):
    lay = rec.layer
    if "step_flops" not in lay or rec.window_s <= 0:
        return None
    rate = lay["steps"] / rec.window_s
    peak = lay["chips"] * lay["peaks"]["bf16_flops_per_s"]
    return 100.0 * lay["step_flops"] * rate / peak
