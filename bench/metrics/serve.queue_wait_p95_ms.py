"""serve.queue_wait_p95_ms: 95th percentile (linear) of ``queued_ms`` over
the ``serve.admit`` spans inside the traced window: the time from a
request's ``submit()`` to the start of its admission
(``launch/serve.py ContinuousEngine._admit``).  None where the trace holds
no program spans (``bench/spans.py``)."""

import numpy as np

import spans


def read(rec):
    admits = spans.in_window(rec.trace, "serve.admit")
    if not admits:
        return None
    return float(np.percentile([sp[3]["queued_ms"] for sp in admits], 95))
