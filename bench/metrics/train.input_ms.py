"""train.input_ms: median duration of the ``train.batch`` spans inside the
traced window: the train loop's call of ``batch_fn(step)``
(``dist/train.py make_resilient_train_loop``).  None where the trace holds
no program spans (``bench/spans.py``)."""

import spans


def read(rec):
    return spans.median_ms(rec.trace, "train.batch")
