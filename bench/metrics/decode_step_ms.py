"""Device time of the decode program (``lm_decode``) per call, over its
calls in the traced window, in ms."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    got = trace_reduce.program_time(run.trace, "lm_decode")
    return got[0] / got[1] * 1e3 if got else None
