"""The plain reference against the program's own forward, at reduced size
on the CPU: a departure of either shows here, not on the chip."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells  # puts bench/ and src/ on the path
import reference
import run
from repro.configs import get_arch
from repro.models import param_specs
from repro.models.layers import logits_head
from repro.models.lm import lm_hidden
from repro.models.moe import moe_ffn


def _sizes(cfg, norm_topk_prob):
    m = {k: getattr(cfg, k) for k in (
        "d_model", "num_heads", "num_kv_heads", "head_dim", "vocab_size",
        "rope_theta", "norm_eps", "moe_top_k")}
    return dict(m, norm_topk_prob=norm_topk_prob)


def _rel_errors(name, seed, norm_topk_prob=True):
    cfg = get_arch(name).reduced()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    p = reference.make_weights(param_specs(cfg), seed)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, 64)).astype(np.int32)
    h, _ = lm_hidden(p, {"tokens": jnp.asarray(toks)}, cfg)
    prog = np.asarray(logits_head(p, h, cfg.vocab_size)[..., :cfg.vocab_size],
                      np.float32)
    ref = np.asarray(reference.hidden(p, jnp.asarray(toks),
                                      _sizes(cfg, norm_topk_prob)))
    ref = ref @ np.asarray(p["lm_head"], np.float32)[:, :cfg.vocab_size]
    return np.abs(ref - prog).max(-1) / np.abs(ref).max(-1)


# The program runs bf16 end to end, the reference float32: each position's
# logits differ by a few tenths of a percent of max|logit| per layer.
@pytest.mark.parametrize("seed", [1, 2])
def test_dense_forward_agrees(seed):
    assert _rel_errors("internlm2-1.8b", seed).max() < 0.05


# A token whose 2nd and 3rd router scores nearly tie may pick another expert
# in bf16 than in float32, and then differs by a whole expert's share; the
# median position must agree as closely as the dense model's.
@pytest.mark.parametrize("seed", [1, 2])
def test_moe_forward_agrees(seed):
    assert np.median(_rel_errors("olmoe-1b-7b", seed)) < 0.05


def test_moe_departure_shows():
    """Gates left unnormalised (the published OLMoE) is another model."""
    assert np.median(_rel_errors("olmoe-1b-7b", 1, False)) > 0.1


def test_moe_layer_agrees():
    p = reference.make_weights(
        jax.eval_shape(lambda: {"router": jnp.zeros((64, 4), jnp.float32),
                                "experts": {
                                    "w1": jnp.zeros((4, 64, 128), jnp.bfloat16),
                                    "w3": jnp.zeros((4, 64, 128), jnp.bfloat16),
                                    "w2": jnp.zeros((4, 128, 64), jnp.bfloat16)}}),
        3)
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 64)).astype(jnp.bfloat16)
    out, _ = moe_ffn(p, x, n_experts=4, top_k=2, capacity_factor=8.0)
    mm, _ = reference._products("f32")
    ref = reference._moe(p, x.astype(jnp.float32)[None],
                         {"moe_top_k": 2, "norm_topk_prob": True}, mm)[0]
    ref = np.asarray(ref)
    assert np.abs(np.asarray(out, np.float32) - ref).max() < 0.01 * np.abs(ref).max()


def test_served_gaps_read_the_served_token():
    cfg = get_arch("internlm2-1.8b").reduced()
    p = reference.make_weights(param_specs(cfg), 4)
    m = _sizes(cfg, False)
    toks = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 32))
    rows = np.arange(32)[None]
    ref = np.asarray(reference.hidden(p, jnp.asarray(toks), m))
    ref = ref[0] @ np.asarray(p["lm_head"], np.float32)[:, :cfg.vocab_size]
    best, worst = ref.argmax(-1)[None], ref.argmin(-1)[None]
    gap, ctl = reference.served_gaps(p, toks, rows, best, m, control=True)
    assert np.abs(gap).max() < 1e-5 and ctl.min() >= 0
    gap, _ = reference.served_gaps(p, toks, rows, worst, m)
    np.testing.assert_allclose(gap[0], ref.max(-1) - ref.min(-1), rtol=1e-4)


def test_seed_keeps_every_bit():
    a = reference.seed_key(2**31 + 5)
    b = reference.seed_key(2**31 + 5 + 2**32)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))


def test_a_vector_that_is_no_norm_scale_draws():
    """A bias (a router's correction bias) draws N(0, 0.1); a norm scale
    1 + N(0, 0.1), a matrix N(0, 1) over sqrt(fan-in), as before."""
    p = reference.make_weights(jax.eval_shape(lambda: {
        "bias": jnp.zeros((4096,), jnp.float32),
        "norm": {"scale": jnp.zeros((4096,), jnp.bfloat16)},
        "w": jnp.zeros((64, 512), jnp.bfloat16)}), 2**31 + 9)
    bias = np.asarray(p["bias"])
    assert bias.dtype == np.float32
    assert abs(bias.mean()) < 0.01 and 0.09 < bias.std() < 0.11
    scale = np.asarray(p["norm"]["scale"], np.float32)
    assert abs(scale.mean() - 1.0) < 0.01 and 0.09 < scale.std() < 0.11
    w = np.asarray(p["w"], np.float32)
    assert 0.9 / 8 < w.std() < 1.1 / 8


# sha256 of every leaf's bytes, in tree order, as the harness drew them
# before a 1-D leaf other than a norm scale could draw.
WEIGHT_DIGESTS = {
    "internlm2-1.8b.chat-swap":
        "8c7910d3d96400dbec380a274b7d6705e8e7016e069cde1b1e9decab53870e5c",
    "olmoe-1b-7b.chat-burst":
        "ddba6c504be638e713bf17c6078d24a939ab83495cf1fd264da48ba94d61909f",
}


@pytest.mark.parametrize("name", sorted(WEIGHT_DIGESTS))
def test_committed_configurations_draw_the_same_weights(name):
    cfg = run.model_config(cells.small_cell(name)["config"])
    p = reference.make_weights(param_specs(cfg), 2**31 + 7)
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(p):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == WEIGHT_DIGESTS[name]
