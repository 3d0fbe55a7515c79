"""The reduction from a profiler trace to the benchmark's numbers."""

import os

import numpy as np
import pytest

import cells  # noqa: F401  (puts bench/ on the path)
import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata")


def test_interval_algebra():
    m = T.merge([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert m == [(0, 2), (3, 4)] and T.length(m) == 3
    assert T.intersect(m, [(1, 3.5)]) == [(1, 2), (3, 3.5)]


def test_idle_gaps_are_named_by_the_host_span_they_fell_in():
    tr = T.Trace(ops=[("fusion", 1.0, 2.0, 0), ("dot", 4.0, 5.0, 0)],
                 modules=[("jit_lm_decode(1)", 1.0, 2.0, 0),
                          ("jit_lm_decode(1)", 4.0, 5.0, 0)],
                 host=[("bench.step", 0.0, 3.5), ("bench.sync", 3.5, 6.0),
                       ("PjitFunction(argmax)", 2.5, 3.5)],
                 devices=1)
    assert T.program_time(tr, "lm_decode") == (2.0, 2)
    assert T.program_time(tr, "lm_prefill") is None
    assert T.busy_s(tr, (0.0, 6.0)) == 2.0
    gaps = dict(T.idle_gaps(tr, (0.0, 6.0)))
    # each gap goes to the innermost host event at its midpoint
    assert gaps == pytest.approx({"bench.step": 1.0,
                                  "PjitFunction(argmax)": 2.0,
                                  "bench.sync": 1.0})
    assert T.top_ops(tr)[0][1] == 1.0


def test_an_unnamed_program_is_found_by_its_dispatches():
    tr = T.Trace(modules=[("jit__unknown(7)", 0.0, 1.0, 0),
                          ("jit__unknown(7)", 2.0, 3.0, 0),
                          ("jit__unknown(9)", 4.0, 4.5, 0)],
                 host=[("PjitFunction(lm_decode)", 0.0, 0.2),
                       ("PjitFunction(lm_decode)", 0.05, 0.1),
                       ("PjitFunction(lm_decode)", 1.0, 1.2),
                       ("PjitFunction(lm_decode)", 1.05, 1.1),
                       ("PjitFunction(lm_prefill)", 3.0, 3.2)],
                 devices=1)
    assert T.dispatches(tr, "lm_decode") == 2
    assert T.program_time(tr, "lm_decode") == (2.0, 2)
    assert T.program_time(tr, "lm_prefill") == (0.5, 1)
    assert T.program_time(tr, "lm_other") is None


def test_offset_maps_the_harness_clock_onto_the_trace():
    tr = T.Trace(host=[("bench.step", 101.0, 102.0),
                       ("bench.sync", 102.0, 102.5)])
    spans = [("bench.step", 0.5, 0.9), ("bench.step", 1.0, 2.0),
             ("bench.sync", 2.0, 2.5)]
    assert T.offset(tr, spans) == 100.0


def test_offset_ignores_the_spans_after_the_trace_stopped():
    """The harness keeps spans past the traced part of the window; the
    trace's spans are found among them by time, not by their count."""
    starts = np.cumsum(np.random.default_rng(3).uniform(0.06, 0.2, 100))
    spans = [("bench.step", a, a + 0.05) for a in starts]
    spans += [("bench.sync", a + 0.05, a + 0.055) for a in starts]
    traced = [(n, a + 1000.0, b + 1000.0) for n, a, b in spans
              if 3.0 <= a < 5.0]
    tr = T.Trace(host=traced)
    assert T.offset(tr, spans) == pytest.approx(1000.0)


def test_a_trace_recorded_on_the_chip():
    """A 0.75 s slice of ``internlm2-1.8b.chat-swap`` on one TPU v5e (nine
    decode steps, a swap-in and a demotion): the numbers the readers take."""
    tr = T.load(os.path.join(DATA, "decode_window.xplane.pb.gz"))
    assert tr.devices == 1
    lo, hi = T.window(tr)
    assert hi - lo == pytest.approx(0.749181, abs=1e-6)
    # the decode program runs as jit__unknown(...): found by its dispatches
    assert T.dispatches(tr, "lm_decode") == 9
    secs, runs = T.program_time(tr, "lm_decode")
    assert runs == 9 and secs / runs == pytest.approx(5.7624e-3, rel=1e-4)
    assert T.busy_s(tr, (lo, hi)) == pytest.approx(0.0518856, rel=1e-5)
    assert T.top_ops(tr)[0][0].startswith("%while")
    gaps = dict(T.idle_gaps(tr, (lo, hi)))
    # the host was swapping KV in (inside submit) or copying a demotion out
    assert max(gaps, key=gaps.get) == "bench.submit"
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.150882, rel=1e-4)
