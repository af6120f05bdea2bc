"""serve.prefill_ms: median of the engine's own ``Request.prefill_ms``
(host clock around the prefill call and its argmax sync) over the requests
of the window."""

import statistics


def read(rec):
    vals = rec.layer.get("prefill_ms")
    return statistics.median(vals) if vals else None
