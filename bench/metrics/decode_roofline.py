"""The decode program's share of its roofline, in %: the least time the
chip needs for a decode call (the configuration's work counts,
``bench/work.py`` by default: the weights the token needs, read once, and
the keys and values it attends, against the peaks in ``bench/peaks.json``),
averaged over the window's decode steps, over the device time per call
that the trace measured."""

import work


def read(run):
    ms = run.metric("decode_step_ms")
    contexts = run.decode_contexts()
    if ms is None or not contexts:
        return None
    need = [work.needed_seconds(*run.work.decode_call(run.cfg, [c]),
                                run.peak)[0]
            for c in contexts]
    return 100.0 * (sum(need) / len(need)) / (ms * 1e-3)
