"""Device-idle time under the program's span
``cnn.put``: the H2D ``jax.device_put`` of each staged group,
per batch the window retired, in ms (``cnn_spans.py``)."""
import cnn_spans


def read(m):
    return cnn_spans.per_batch_ms(m, "put")
