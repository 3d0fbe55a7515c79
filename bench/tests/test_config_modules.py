"""A configuration file names its own reference module, work counts module
and size names, and the harness takes them with no edit of its own; without
those keys it runs ``bench/reference.py`` and ``bench/work.py`` as before."""

import os
from types import SimpleNamespace

import jax
import pytest

import cells
import reference
import run
import work

CELLS = ["internlm2-1.8b.chat-swap", "olmoe-1b-7b.chat-burst"]

# What the reference was handed before a configuration could name its own.
DEFAULT_FIELDS = ("d_model", "num_heads", "num_kv_heads", "head_dim",
                  "vocab_size", "rope_theta", "norm_eps", "moe_top_k")


@pytest.mark.parametrize("name", CELLS)
def test_committed_configurations_use_the_default_modules(name):
    config = run.load_cell(name)["config"]
    assert run.config_module(config, "reference") is reference
    assert reference.__file__ == os.path.join(run.BENCH, "reference.py")
    assert run.config_module(config, "work") is work
    m = run.reference_sizes(run.model_config(config), config)
    assert set(m) == set(DEFAULT_FIELDS) | set(config["semantics"])


def _own_modules_cell(ref):
    cell = cells.small_cell(CELLS[0])
    conf = cell["config"]
    conf.update(reference=ref, work="tests/work_fixed",
                size_fields={"n_layer": "num_layers"},
                sizes={"n_layer": conf["overrides"]["num_layers"]})
    return cell


def _run(cell, monkeypatch):
    made = []

    class Seen(run.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            made.append(self)
    monkeypatch.setattr(run, "Run", Seen)
    out = run.run_cell(cell, 21, 2.0, False, jax.devices()[:1],
                       lambda line: None)
    return out, made[0]


def test_a_named_reference_decides_correct(monkeypatch):
    cell = _own_modules_cell("tests/ref_layers")
    own = run.bench_module("tests/ref_layers")
    own.SEEN.clear()
    out, seen = _run(cell, monkeypatch)
    assert out["correct"], out["checks"]
    assert seen.work is run.bench_module("tests/work_fixed")
    assert own.SEEN, "the named module's hidden never ran"
    m = own.SEEN[-1]
    assert set(m) == set(own.FIELDS) | set(cell["config"]["semantics"])
    assert m["num_layers"] == cell["config"]["overrides"]["num_layers"]


def test_a_wrong_named_reference_makes_a_sound_run_incorrect(monkeypatch):
    out, _ = _run(_own_modules_cell("tests/ref_layers_short"), monkeypatch)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_a_mapped_size_is_checked():
    config = {"model": "internlm2-1.8b", "sizes": {"n_layer": 24},
              "size_fields": {"n_layer": "num_layers"}}
    assert run.model_config(config).num_layers == 24
    config["sizes"]["n_layer"] = 23
    with pytest.raises(ValueError, match="n_layer runs as 24"):
        run.model_config(config)


@pytest.mark.parametrize("size_fields", [{}, {"kv_lora_rank": "kv_lora_rank"}],
                         ids=["unmapped", "missing-field"])
def test_a_size_without_a_field_raises(size_fields):
    config = {"model": "internlm2-1.8b", "sizes": {"kv_lora_rank": 512},
              "size_fields": size_fields}
    with pytest.raises(ValueError, match="kv_lora_rank"):
        run.model_config(config)


def test_readers_count_with_the_named_work_module():
    """``mfu`` and ``decode_roofline`` take their counts from ``run.work``:
    here 1 GFLOP and 1 MB a decoded token, 10 GFLOP a prompt token."""
    t0 = 100.0
    served = [SimpleNamespace(turn=SimpleNamespace(due_s=0.0, prompt=[0] * 8),
                              done=t0 + 0.5, rows=[8, 9],
                              req=SimpleNamespace(prefix_hit=False))]
    r = run.Run(cfg=None, work=run.bench_module("tests/work_fixed"),
                device_kind="TPU v5 lite", served=served,
                window={"t0": t0, "t1": t0 + 1.0, "queued_due_s": []})
    peak = work.peaks("TPU v5 lite")
    assert r.metric("mfu") == pytest.approx(
        100.0 * (2 * 1e9 + 8 * 1e10) / (0.5 * peak["flops_bf16"]))
    r._cache["decode_step_ms"] = 1.0
    assert r.metric("decode_roofline") == pytest.approx(
        100.0 * (1e9 / peak["flops_bf16"]) / 1e-3)
