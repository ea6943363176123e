"""Device-idle time under the program's span
``cnn.launch``: the compiled forward's lookup and its dispatch,
per batch the window retired, in ms (``cnn_spans.py``)."""
import cnn_spans


def read(m):
    return cnn_spans.per_batch_ms(m, "launch")
