"""Median queue wait, in ms, of the requests of the batches fetched in the
window: the engine's ``t_admit - t_submit``, carried by the ``cnn.put``
span of the batch each request was admitted to (``cnn_spans.py``)."""
import numpy as np

import cnn_spans


def read(m):
    waits = cnn_spans.reading(m).queue_waits_ms
    return float(np.median(waits)) if waits else None
