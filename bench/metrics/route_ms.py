"""Router host time per request, in ms: the self time of the ``router.*``
spans (``enqueue``, ``tick``, ``complete``), the payload moves and other
program spans inside them left out, over the requests the traced window
served (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_request_ms(run.trace, "router.", self_time=True)
