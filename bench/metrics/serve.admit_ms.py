"""serve.admit_ms: median duration of the ``serve.admit`` spans inside the
traced window: one admission, its prefill, first-token read and the slot
updates of the cache (``launch/serve.py ContinuousEngine._admit``).  None
where the trace holds no program spans (``bench/spans.py``)."""

import spans


def read(rec):
    return spans.median_ms(rec.trace, "serve.admit")
