"""Device-idle time under the program's span
``cnn.stage`` self time: admission, the host buffer and the copy of
each image into it, without the ``cnn.put`` inside it,
per batch the window retired, in ms (``cnn_spans.py``)."""
import cnn_spans


def read(m):
    return cnn_spans.per_batch_ms(m, "stage")
