"""serve.decode_bookkeep_ms: median duration of the
``serve.decode.bookkeep`` spans inside the traced window: a decode step's
per-slot token updates, retirements, deadlines and length mask
(``launch/serve.py ContinuousEngine._decode_once``).  None where the trace
holds no program spans (``bench/spans.py``)."""

import spans


def read(rec):
    return spans.median_ms(rec.trace, "serve.decode.bookkeep")
