"""Device-idle time under the program's span
``cnn.retire``: the screen, bookkeeping and counters after the
fetch,
per batch the window retired, in ms (``cnn_spans.py``)."""
import cnn_spans


def read(m):
    return cnn_spans.per_batch_ms(m, "retire")
