"""Demotion time per request, in ms: the ``payload.demote`` spans (KV
copied off the device to host memory, or spilled further down) inside the
traced window, over the requests it served (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_request_ms(run.trace, "payload.demote", self_time=False)
