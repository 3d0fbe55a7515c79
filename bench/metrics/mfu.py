"""Whole-step model FLOP utilisation, in %: the FLOPs the window's served
work requires (the configuration's work counts, ``bench/work.py`` by
default: each prefill run and each decode step, active experts only) over
the time with at least one request outstanding times the chip's bf16
peak."""

import trace_reduce


def read(run):
    busy = trace_reduce.length(run.outstanding())
    if busy <= 0:
        return None
    flops = sum(run.work.decode_call(run.cfg, [c])[0]
                for c in run.decode_contexts())
    flops += sum(run.work.prefill_call(run.cfg, n)[0]
                 for n in run.prefill_lengths())
    return 100.0 * flops / (busy * run.peak["flops_bf16"])
