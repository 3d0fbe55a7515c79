"""The readers of the program's own spans, on hand-built traces whose self
times, counts and program times are known."""

import pytest

import cells  # noqa: F401  (puts bench/ on the path)
import run
import spans
import trace_reduce as T

# Harness spans bound the traced window to [0, 10].
WINDOW = [("bench.submit", 0.0, 0.5), ("bench.step", 1.0, 10.0)]


def _trace(host, modules=()):
    return T.Trace(host=WINDOW + list(host), modules=list(modules),
                   devices=1)


# Two requests end inside the window, a third after it.  Router self
# time: enqueue 0.1 + tick 0.4 - 0.1 (promote) - 0.2 (demote) + complete
# 0.6 - 0.3 (its tick) + that tick 0.3; the late tick is not counted.
HOST = [
    ("router.enqueue", 0.1, 0.2),
    ("router.tick", 0.2, 0.6),
    ("payload.promote", 0.25, 0.35),
    ("payload.demote", 0.4, 0.6),
    ("serve.step", 1.0, 9.0),
    ("serve.request", 1.0, 4.0),
    ("serve.restore", 1.0, 1.1),
    ("serve.decode", 1.1, 3.0),
    ("serve.token", 1.1, 1.6),
    ("PjitFunction(lm_decode)", 1.2, 1.3),
    ("serve.token", 1.6, 2.0),
    ("payload.put", 3.0, 4.0),
    ("router.complete", 4.0, 4.6),
    ("router.tick", 4.1, 4.4),
    ("serve.request", 5.0, 8.0),
    ("serve.prefill", 5.0, 6.0),
    ("serve.decode", 6.0, 7.0),
    ("serve.token", 6.0, 6.3),
    ("payload.demote", 7.5, 7.9),
    ("serve.request", 9.5, 10.5),
    ("router.tick", 10.0, 10.5),
]


def test_self_time_leaves_out_the_program_spans_inside():
    got = {(n, a): own for n, a, _, own in spans.program_spans(_trace(HOST))}
    assert got[("router.tick", 0.2)] == pytest.approx(0.1)
    assert got[("router.complete", 4.0)] == pytest.approx(0.3)
    # JAX's own events are not program spans: the token keeps its time
    assert got[("serve.token", 1.1)] == pytest.approx(0.5)
    assert got[("serve.request", 1.0)] == pytest.approx(3.0 - 0.1 - 1.9 - 1.0)
    assert got[("serve.step", 1.0)] == pytest.approx(8.0 - 3.0 - 0.6 - 3.0)
    # a span that ends past the window is not counted
    assert ("router.tick", 10.0) not in got and ("serve.request", 9.5) \
        not in got


def test_route_ms_is_router_self_time_per_request():
    r = run.Run(trace=_trace(HOST))
    want = (0.1 + 0.1 + 0.3 + 0.3) / 2 * 1e3
    assert r.metric("route_ms") == pytest.approx(want)
    assert r.metric("route_ms.overload") == pytest.approx(want)


def test_decode_host_ms_is_the_mean_token_span():
    r = run.Run(trace=_trace(HOST))
    assert r.metric("decode_host_ms") == pytest.approx(
        (0.5 + 0.4 + 0.3) / 3 * 1e3)
    assert r.metric("decode_host_ms.overload") == r.metric("decode_host_ms")


def test_demote_ms_is_demotion_time_per_request():
    r = run.Run(trace=_trace(HOST))
    assert r.metric("demote_ms") == pytest.approx((0.2 + 0.4) / 2 * 1e3)
    assert r.metric("demote_ms.overload") == r.metric("demote_ms")


def test_prefill_ms_is_device_time_per_run_over_both_lengths():
    modules = [("jit_lm_prefill(1)", 5.0, 5.02, 0),
               ("jit_lm_prefill(2)", 6.0, 6.05, 0),
               ("jit_lm_prefill(2)", 7.0, 7.05, 0),
               ("jit_lm_decode(3)", 8.0, 8.01, 0)]
    r = run.Run(trace=_trace(HOST, modules))
    assert r.metric("prefill_ms") == pytest.approx(0.12 / 3 * 1e3)
    assert run.Run(trace=_trace(HOST, modules[3:])).metric(
        "prefill_ms") is None


@pytest.mark.parametrize("metric", ["route_ms", "decode_host_ms",
                                    "demote_ms", "prefill_ms"])
def test_a_trace_without_program_spans_reads_nothing(metric):
    """The parent program opens no spans and names no program: the readers
    return None and do not raise; so do runs without a trace."""
    assert run.Run(trace=_trace([])).metric(metric) is None
    assert run.Run(trace=None).metric(metric) is None
    # a trace that caught no harness span has no window: nothing either
    assert run.Run(trace=T.Trace(host=HOST[4:8])).metric(metric) is None
