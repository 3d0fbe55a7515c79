"""Device time of the prefill program (``lm_prefill``) per run, over its
runs in the traced window and both prompt lengths, in ms."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    got = trace_reduce.program_time(run.trace, "lm_prefill")
    return got[0] / got[1] * 1e3 if got else None
