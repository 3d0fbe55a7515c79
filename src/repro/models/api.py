"""Unified model API: init / input specs / loss / prefill / decode / steps.

Dispatches on architecture family (decoder-only LM vs enc-dec) and provides
``input_specs`` — ShapeDtypeStruct stand-ins for every model input of every
(arch x shape) cell, the dry-run contract from the assignment.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig
from ..optim.adamw import (
    AdamWConfig,
    adamw8bit_init,
    adamw8bit_update,
    adamw_init,
    adamw_update,
    cosine_schedule,
)

OPT8BIT_PARAM_THRESHOLD = 100e9  # >100B params: 8-bit AdamW moments


def use_8bit_opt(cfg: ArchConfig) -> bool:
    return cfg.param_count() > OPT8BIT_PARAM_THRESHOLD
from . import encdec, lm
from .sharding import ShardCtx

BF16 = jnp.bfloat16
I32 = jnp.int32


def is_encdec(cfg: ArchConfig) -> bool:
    return cfg.encoder_layers > 0


def attn_chunk(seq_len: int) -> int:
    if seq_len >= 1 << 15:
        return 512
    return min(1024, max(128, seq_len))


# ------------------------------------------------------------------- init
def init_params(cfg: ArchConfig, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    if is_encdec(cfg):
        return encdec.encdec_init(key, cfg)
    return lm_init_with_frontend(key, cfg)


def lm_init_with_frontend(key, cfg: ArchConfig):
    return lm.lm_init(key, cfg)


def param_specs(cfg: ArchConfig):
    """Pytree of ShapeDtypeStruct (no allocation) for the full-size model."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ------------------------------------------------------------- input specs
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _tree_sds(tree):
    return jax.tree_util.tree_map(lambda x: _sds(x.shape, x.dtype), tree)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of this (arch, shape) cell.

    train:   {tokens} (+audio_embeds / patch_embeds for stub frontends)
    prefill: same as train inputs
    decode:  {token, pos, caches} — one new token against a seq_len cache.
    """
    B, S = shape.global_batch, shape.seq_len
    if is_encdec(cfg):
        st = encdec.text_len(S)
        if shape.kind in ("train", "prefill"):
            return {
                "audio_embeds": _sds((B, S, cfg.d_model), BF16),
                "tokens": _sds((B, st), I32),
            }
        caches = jax.eval_shape(
            lambda: encdec.encdec_cache_init(cfg, B, S, S)
        )
        return {"token": _sds((B,), I32), "pos": _sds((), I32),
                "caches": _tree_sds(caches)}

    if shape.kind in ("train", "prefill"):
        out: Dict[str, Any] = {}
        if cfg.frontend == "vision":
            P = min(cfg.num_patches, S // 2)
            out["patch_embeds"] = _sds((B, P, cfg.d_model), BF16)
            out["tokens"] = _sds((B, S - P), I32)
        else:
            out["tokens"] = _sds((B, S), I32)
        return out

    caches = jax.eval_shape(lambda: lm.lm_cache_init(cfg, B, S))
    return {"token": _sds((B,), I32), "pos": _sds((), I32),
            "caches": _tree_sds(caches)}


def synth_inputs(cfg: ArchConfig, shape: ShapeConfig, key=None) -> Dict[str, Any]:
    """Concrete random inputs matching ``input_specs`` (smoke tests)."""
    key = key if key is not None else jax.random.PRNGKey(1)
    specs = input_specs(cfg, shape)

    def materialize(s):
        if s.dtype == I32:
            if s.shape == ():
                return jnp.asarray(min(shape.seq_len - 1, 7), I32)
            return jax.random.randint(key, s.shape, 0, cfg.vocab_size, I32)
        return jnp.zeros(s.shape, s.dtype)

    out = jax.tree_util.tree_map(materialize, specs)
    if "caches" in out:
        # decode smoke: caches start zeroed (valid: masked by position)
        pass
    return out


# ------------------------------------------------------------- step fns
def make_loss_fn(cfg: ArchConfig, shape: ShapeConfig, ctx: ShardCtx = ShardCtx()):
    chunk = attn_chunk(shape.seq_len)
    if is_encdec(cfg):
        return functools.partial(encdec.encdec_loss, cfg=cfg, ctx=ctx, chunk=chunk)
    return functools.partial(lm.lm_loss, cfg=cfg, ctx=ctx, chunk=chunk)


def make_train_step(cfg: ArchConfig, shape: ShapeConfig, ctx: ShardCtx = ShardCtx(),
                    opt: AdamWConfig = AdamWConfig(), total_steps: int = 10_000,
                    microbatches: Optional[int] = None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches`` > 1 runs gradient accumulation: the global batch is
    split along dim 0 and fwd+bwd runs per slice under ``lax.scan`` with an
    f32 grad accumulator — bounding activation memory for the largest stacks
    (qwen3-235B peaks ~40 GiB/device without it).
    """
    loss_fn = make_loss_fn(cfg, shape, ctx)
    n_mb = microbatches if microbatches is not None else cfg.train_microbatches(
        shape.global_batch)

    def grad_of(params, mb):
        return jax.value_and_grad(lambda p: loss_fn(p, mb), has_aux=True)(params)

    def train_step(params, opt_state, batch):
        eightbit = use_8bit_opt(cfg)
        if n_mb == 1:
            (loss, extras), grads = grad_of(params, batch)
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:]), batch
            )
            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def body(acc, mb):
                acc_g, acc_loss, acc_aux = acc
                (_, ex), g = grad_of(params, mb)
                acc_g = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(jnp.float32) / n_mb, acc_g, g
                )
                return (acc_g, acc_loss + ex["loss"] / n_mb,
                        acc_aux + ex.get("aux", jnp.zeros(())) / n_mb), None

            (grads, loss_m, aux_m), _ = jax.lax.scan(
                body, (acc0, jnp.zeros(()), jnp.zeros(())), mbs
            )
            loss, extras = loss_m, {"loss": loss_m, "aux": aux_m}
        # schedule runs on the post-increment step (lr > 0 from step one)
        lr_scale = cosine_schedule(
            opt_state["step"] + 1, warmup=min(100, max(1, total_steps // 10)),
            total=total_steps)
        update = adamw8bit_update if eightbit else adamw_update
        params, opt_state, om = update(grads, opt_state, params, opt, lr_scale)
        metrics = {"loss": extras["loss"], "total_loss": loss, **om}
        return params, opt_state, metrics

    return train_step


def _named(fn: Callable, **bound: Any) -> Callable:
    """``fn`` with ``bound`` fixed, under ``fn``'s name: jitted, it compiles
    as ``jit_<fn>`` (a ``functools.partial`` compiles as ``jit__unknown``),
    so a profile names the program it ran."""
    def step(params, batch):
        return fn(params, batch, **bound)
    step.__name__ = step.__qualname__ = fn.__name__
    return step


def make_prefill_step(cfg: ArchConfig, shape: ShapeConfig, ctx: ShardCtx = ShardCtx()):
    chunk = attn_chunk(shape.seq_len)
    prefill = encdec.encdec_prefill if is_encdec(cfg) else lm.lm_prefill
    return _named(prefill, cfg=cfg, ctx=ctx, chunk=chunk)


def make_decode_step(cfg: ArchConfig, ctx: ShardCtx = ShardCtx()):
    decode = encdec.encdec_decode if is_encdec(cfg) else lm.lm_decode
    return _named(decode, cfg=cfg, ctx=ctx)


def make_step(cfg: ArchConfig, shape: ShapeConfig, ctx: ShardCtx = ShardCtx()):
    """The step function a dry-run cell lowers, by shape kind."""
    if shape.kind == "train":
        return make_train_step(cfg, shape, ctx)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape, ctx)
    return make_decode_step(cfg, ctx)


def init_opt_state(params, cfg: Optional[ArchConfig] = None):
    if cfg is not None and use_8bit_opt(cfg):
        return adamw8bit_init(params)
    return adamw_init(params)


def cache_init(cfg: ArchConfig, batch: int, cap: int):
    if is_encdec(cfg):
        return encdec.encdec_cache_init(cfg, batch, cap, cap)
    return lm.lm_cache_init(cfg, batch, cap)
