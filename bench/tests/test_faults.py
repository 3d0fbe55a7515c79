"""A whole run at reduced size on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the served path broken underneath,
or with the reference's fp8 control in the program's place, it does not.
The limits are the committed ones; at this size they separate as on the
chip.
"""

import jax
import jax.numpy as jnp
import pytest

import cells
import run
from repro.diffusion import payload
from repro.runtime import serve_loop

CELLS = ["internlm2-1.8b.chat-swap", "olmoe-1b-7b.chat-burst"]


def _run(name, seed=11, control=False):
    return run.run_cell(cells.small_cell(name), seed, 2.0, False,
                        jax.devices()[:1], lambda line: None, control=control)


def _broken_decode(monkeypatch, wrap):
    make = serve_loop.make_decode_step
    monkeypatch.setattr(serve_loop, "make_decode_step",
                        lambda cfg, ctx: wrap(make(cfg, ctx)))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The fp8 reference's first choices, put in the served tokens' place
    and judged by the harness's own verdict, come out not correct where the
    program's own tokens come out correct."""
    out = _run(name, seed=12, control=True)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"], out["control"]["checks"]
    limits = run.load_cell(name)["config"]["limits"]
    assert set(out["control"]["checks"]) >= set(limits)


@pytest.mark.parametrize("name", CELLS)
def test_altered_token_is_caught(monkeypatch, name):
    """Every served token is the next id after the best one."""
    def wrap(decode):
        def step(params, batch):
            logits, caches = decode(params, batch)
            return jnp.roll(logits, 1, axis=-1), caches
        return step
    _broken_decode(monkeypatch, wrap)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_unchanged_state_is_caught(monkeypatch, name):
    """The decode step hands back the cache it was given."""
    def wrap(decode):
        def step(params, batch):
            logits, _ = decode(params, batch)
            return logits, batch["caches"]
        return step
    _broken_decode(monkeypatch, wrap)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_altered_swap_in_is_caught(monkeypatch, name):
    """KV swapped back in from host DRAM arrives halved."""
    to_device = payload.RealPayload._to_device

    def halved(self, leaves):
        return [x * jnp.asarray(0.5, x.dtype) for x in to_device(self, leaves)]
    monkeypatch.setattr(payload.RealPayload, "_to_device", halved)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name,nth", [(CELLS[0], 60), (CELLS[1], 40)])
def test_lost_request_is_not_correct(monkeypatch, name, nth):
    """A request the server never serves counts as failed, whether the
    window drains or closes on a backlog."""
    serve = serve_loop.DiffusionServer._run_request
    calls = []

    def skipping(self, replica, routed):
        calls.append(routed)
        if len(calls) != nth:
            serve(self, replica, routed)
    monkeypatch.setattr(serve_loop.DiffusionServer, "_run_request", skipping)
    monkeypatch.setattr(run, "GRACE_S", 0.5)
    out = _run(name)
    assert len(calls) > nth
    assert out["failed"] == 1 and not out["correct"]


def test_backlog_at_the_close_is_left_unserved():
    """Offered above capacity, the window closes at its length: the
    backlog is neither served nor failed, and ``tokens_per_s`` counts the
    tokens of the requests served, over the window as it closed."""
    cell = cells.small_cell(CELLS[1])
    cell["mix"]["knee_req_s"] = 400.0
    lines = []
    out = run.run_cell(cell, 13, 2.0, False, jax.devices()[:1], lines.append)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    window = next(x for x in lines if x.startswith("window:"))
    served = int(window.split(" due, ")[1].split(" served")[0])
    queued = int(window.split(" lost, ")[1].split(" queued")[0])
    assert queued > 0 and served + queued == out["attempted"]
    window_s = float(window.split(" queued at the close, ")[1].split(" s;")[0])
    assert window_s >= 2.0
    tokens = out["metrics"]["tokens_per_s"]["value"] * window_s
    low, high = cell["mix"]["new_tokens"]
    assert low * served - 1e-6 <= tokens <= high * served + 1e-6
