"""Device-idle time under the program's span
``cnn.fetch``: the blocking fetch of the logits (and the ABFT
verdict, when armed),
per batch the window retired, in ms (``cnn_spans.py``)."""
import cnn_spans


def read(m):
    return cnn_spans.per_batch_ms(m, "fetch")
