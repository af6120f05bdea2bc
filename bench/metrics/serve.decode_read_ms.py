"""serve.decode_read_ms: median duration of the ``serve.decode.read`` spans
inside the traced window: the per-slot reads of a decode step's argmax
vector to the host (``launch/serve.py ContinuousEngine._decode_once``).
None where the trace holds no program spans (``bench/spans.py``)."""

import spans


def read(rec):
    return spans.median_ms(rec.trace, "serve.decode.read")
