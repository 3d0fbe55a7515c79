"""Host time per generated token, in ms: the mean ``serve.token`` span (the
decode program's dispatch and the argmax that feeds the next token), inside
the traced window (``bench/spans.py``).  Beside ``decode_step_ms``, the
device's time for the same step, it says whether the host or the device
sets the pace of decode."""

import spans


def read(run):
    return spans.mean_ms(run.trace, "serve.token")
