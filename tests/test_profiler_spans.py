"""The program's own spans on the profiler's timeline.

A reduced ``DiffusionServer`` with the real payload plane (three sessions
over two HBM slots, so sessions are demoted and swapped back in) runs under
``jax.profiler``; the trace is read back with ``ProfileData``.  The spans
must nest as the layers do, carry constant names, and, where an ``obs``
ring is held, hold the same intervals as the ring's spans.
"""

from __future__ import annotations

import glob

import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch
from repro.models import init_params, make_decode_step, make_prefill_step
from repro.models.api import cache_init
from repro.obs import Observability
from repro.obs import trace as obs_trace
from repro.obs.trace import TraceBuffer, span
from repro.runtime.serve_loop import DiffusionServer

CFG = get_arch("internlm2-1.8b").reduced()
NAMES = {"serve.step", "serve.request", "serve.restore", "serve.prefill",
         "serve.decode", "serve.token", "router.enqueue", "router.tick",
         "router.complete", "payload.put", "payload.get", "payload.promote",
         "payload.demote"}
PREFIXES = ("serve.", "router.", "payload.")


def _serve(obs):
    srv = DiffusionServer(CFG, policy="good-cache-compute", max_replicas=1,
                          min_replicas=1, cache_cap=48, max_sessions=2,
                          host_cache_sessions=4, seed=1, payload="real",
                          obs=obs)
    rng = np.random.default_rng(0)
    prompts = {f"s{i}": rng.integers(0, CFG.vocab_size, size=(12,))
               for i in range(3)}
    for _ in range(3):
        for sid, p in prompts.items():      # 3 sessions > 2 HBM slots
            srv.submit(sid, p, max_new_tokens=2)
        srv.step()
    return srv


def _trace(tmp, fn):
    """Run ``fn`` under the profiler; its result and the program's spans as
    ``(name, start_s, end_s, metadata)`` in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        got = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9,
                     {k: v for k, v in e.stats})
                    for e in line.events if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda s: (s[1], -s[2])), got


def _profiled(tmp, obs):
    """Serve under the profiler: the program's spans and the server."""
    return _trace(tmp, lambda: _serve(obs))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    _serve(None)                            # compile outside the traces
    with_obs = Observability()
    traced_obs = _profiled(tmp_path_factory.mktemp("obs"), with_obs)
    traced_none = _profiled(tmp_path_factory.mktemp("none"), None)
    return {"obs": (traced_obs, with_obs), "none": (traced_none, None)}


def _inside(s, outer):
    return [o for o in outer if o[1] <= s[1] and s[2] <= o[2] and o is not s]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("mode", ["obs", "none"])
def test_spans_nest_as_the_layers_do(runs, mode):
    (spans, srv), _ = runs[mode]
    assert srv.stats.swap_ins >= 1 and srv.stats.prefills >= 3
    steps = _named(spans, "serve.step")
    requests = _named(spans, "serve.request")
    decodes = _named(spans, "serve.decode")
    tokens = _named(spans, "serve.token")
    assert len(steps) == 3 and len(requests) == srv.stats.served == 9
    assert len(tokens) == srv.stats.decode_steps == 18
    for tok in tokens:
        dec, = _inside(tok, decodes)
        req, = _inside(dec, requests)
        assert _inside(req, steps)
    assert len(_named(spans, "serve.prefill")) == srv.stats.prefills
    moves = _named(spans, "payload.promote") + _named(spans, "payload.demote")
    assert _named(spans, "payload.promote") and _named(spans,
                                                       "payload.demote")
    for mv in moves:
        assert _inside(mv, _named(spans, "router.tick") + steps)
    # the swap-in bytes were moved inside a promotion
    assert srv.measured.rows() and len(_named(spans, "payload.promote")) \
        >= srv.stats.swap_ins
    for name in ("router.enqueue", "router.tick", "router.complete",
                 "payload.put"):
        assert _named(spans, name), name


def test_span_names_are_bare_constants(runs):
    (spans, _), _ = runs["obs"]
    assert {s[0] for s in spans} <= NAMES
    assert {"serve.step", "serve.request", "serve.token",
            "router.tick"} <= {s[0] for s in spans}
    requests = _named(spans, "serve.request")
    # the request id rides as metadata, never in the name
    assert sorted(s[3]["request_id"] for s in requests) == list(range(9))
    assert all(set(s[3]) <= {"request_id"} for s in spans)
    for s in spans:
        if s[0] in ("serve.prefill", "serve.decode", "serve.restore"):
            req, = _inside(s, requests)
            assert s[3] == req[3]
    assert not any(s[0].startswith("bench.") for s in spans)


def test_programs_are_named_after_the_model_step():
    params = init_params(CFG, jax.random.PRNGKey(0))
    caches = cache_init(CFG, 1, 32)
    decode = jax.jit(make_decode_step(CFG)).lower(
        params, {"token": np.zeros((1,), np.int32), "pos": np.int32(3),
                 "caches": caches}).as_text()
    shape = ShapeConfig("serve", "prefill", 32, 1)
    prefill = jax.jit(make_prefill_step(CFG, shape)).lower(
        params, {"tokens": np.zeros((1, 16), np.int32)}).as_text()
    assert decode.splitlines()[0].startswith("module @jit_lm_decode ")
    assert prefill.splitlines()[0].startswith("module @jit_lm_prefill ")


def test_ring_spans_hold_the_profiler_intervals(runs):
    (spans, srv), obs = runs["obs"]
    ring = obs.trace.spans()
    pairs = [("prefill", "compute", "serve.prefill"),
             ("decode", "compute", "serve.decode")]
    got = {}
    for ring_name, phase, name in pairs:
        got[name] = ([s for s in ring if s["name"] == ring_name
                      and s["phase"] == phase], _named(spans, name))
    swap = [s for s in ring if s["phase"] == "payload"
            and s["request_id"] >= 0 and s["detail"] == ["dram", "hbm"]]
    assert len(swap) == srv.stats.swap_ins >= 1
    restores = _named(spans, "serve.restore")
    # a restore span is recorded in the ring for swap-ins only: match each
    # by request id through the enclosing serve.request
    rid_of = {}
    for r in restores:
        req, = _inside(r, _named(spans, "serve.request"))
        rid_of[req[3]["request_id"]] = r
    got["serve.restore"] = (swap, [rid_of[s["request_id"]] for s in swap])
    decode_ring, decode_prof = got["serve.decode"]
    assert len(decode_ring) == len(decode_prof) == 9
    # one clock offset maps the ring's time.time() onto the trace
    shift = decode_prof[0][1] - decode_ring[0]["start_s"]
    for name, (ring_spans, prof) in got.items():
        assert len(ring_spans) == len(prof), name
        for r, p in zip(ring_spans, prof):
            assert abs(r["start_s"] + shift - p[1]) < 2e-3, name
            assert abs(r["end_s"] + shift - p[2]) < 2e-3, name


def test_no_ring_records_without_obs(runs, monkeypatch, tmp_path):
    def boom(*a, **k):
        raise AssertionError("TraceBuffer.record called on the no-op path")
    monkeypatch.setattr(TraceBuffer, "record", boom)
    spans, srv = _profiled(tmp_path, None)
    assert srv.stats.served == 9 and srv.obs is None
    assert {"serve.prefill", "serve.decode", "serve.restore"} <= {
        s[0] for s in spans}


def test_span_without_a_ring_is_the_bare_annotation():
    ann = span("serve.token")
    assert type(ann) is jax.profiler.TraceAnnotation
    with span("serve.request", request_id=3):
        pass
    ring = TraceBuffer()
    with span("serve.decode", ring, 4, "decode", "compute", "r0",
              "dispatch") as sp:
        sp.detail = (7,)
    with span("serve.restore", ring, 5, "kv:s", "payload") as sp:
        sp.detail = None                    # nothing for the ring
    with pytest.raises(KeyError):           # nor for a scope that raised
        with span("serve.prefill", ring, 6, "prefill", "compute"):
            raise KeyError("kv:s")
    rec, = ring.spans()
    assert (rec["request_id"], rec["name"], rec["phase"], rec["replica"],
            rec["parent"], rec["detail"]) == (4, "decode", "compute", "r0",
                                              "dispatch", [7])
    assert 0.0 <= rec["end_s"] - rec["start_s"] < 1.0


def test_spans_need_no_jax(monkeypatch):
    """Without JAX the helper falls back to a no-op annotation and still
    records the ring span."""
    import builtins
    real_import = builtins.__import__

    def no_jax(name, *a, **k):
        if name.startswith("jax"):
            raise ImportError(name)
        return real_import(name, *a, **k)
    monkeypatch.setattr(obs_trace, "_Annotation", None)
    monkeypatch.setattr(builtins, "__import__", no_jax)
    ring = TraceBuffer()
    with span("router.tick"):
        pass
    with span("serve.prefill", ring, 1, "prefill", "compute", detail=(3,)):
        pass
    assert obs_trace._Annotation is obs_trace._NoAnnotation
    monkeypatch.undo()
    assert ring.spans()[0]["detail"] == [3]


def test_payload_moves_are_named_by_direction(tmp_path):
    """Toward the top tier is a promotion, away from it (a disk spill
    included) a demotion; a put and a peer's read have spans of their
    own."""
    from repro.diffusion.payload import RealPayload

    def moves():
        p = RealPayload("t", spill_dir=str(tmp_path / "spill"))
        p.put("kv:a", np.arange(256, dtype=np.float32), "hbm")
        for tier in ("dram", "disk", "dram", "hbm", "disk", "hbm"):
            p.moved("kv:a", tier)
        p.moved("kv:a", "hbm")              # no move: no span
        return p.get("kv:a")

    spans, got = _trace(tmp_path / "trace", moves)
    assert np.array_equal(got, np.arange(256, dtype=np.float32))
    assert [s[0] for s in spans] == [
        "payload.put", "payload.demote", "payload.demote", "payload.promote",
        "payload.promote", "payload.demote", "payload.promote",
        "payload.get"]
