"""serve.decode_step_ms: median of the engine's own
``ContinuousEngine.decode_ms`` (host clock around one decode step and its
argmax sync) over the decode steps of the window."""

import statistics


def read(rec):
    vals = rec.layer.get("decode_ms")
    return statistics.median(vals) if vals else None
