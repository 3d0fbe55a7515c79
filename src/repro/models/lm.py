"""Decoder-only LM over heterogeneous layer patterns.

Supports every assigned non-enc-dec architecture through the per-layer
pattern: 'A' full attention, 'L' windowed/local attention, 'R' RG-LRU
recurrent block, 'W' RWKV6 block — with dense or MoE FFNs.  The layer stack
runs as ``lax.scan`` over repeating *groups* (HLO stays small for 94-layer
stacks), with the non-multiple remainder unrolled; the group body is
``jax.checkpoint``-rematerialized in training.

Three entry points: ``lm_loss`` (train), ``lm_prefill`` (full-sequence +
cache build), ``lm_decode`` (single token against caches).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import rglru as rg
from . import rwkv as rw
from .layers import (
    F32,
    attention_block,
    attn_init,
    chunked_lm_loss,
    dense_init,
    embed_init,
    logits_head,
    mlp,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    softmax_xent,
)
from .moe import moe_ffn, moe_ffn_sharded, moe_init
from .sharding import ShardCtx


def group_pattern(cfg: ArchConfig) -> Tuple[str, ...]:
    return cfg.layer_pattern if cfg.layer_pattern else ("A",)


def group_counts(cfg: ArchConfig) -> Tuple[int, int]:
    g = len(group_pattern(cfg))
    return cfg.num_layers // g, cfg.num_layers % g


# ---------------------------------------------------------------- init
def block_init(key, kind: str, cfg: ArchConfig):
    ks = jax.random.split(key, 3)
    p: Dict = {"norm1": rmsnorm_init(cfg.d_model), "norm2": rmsnorm_init(cfg.d_model)}
    if kind in ("A", "L"):
        p["attn"] = attn_init(ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
        if cfg.num_experts:
            p["moe"] = moe_init(ks[1], cfg.d_model, cfg.d_ff, cfg.num_experts)
        else:
            p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    elif kind == "R":
        p["rglru"] = rg.rglru_init(ks[0], cfg.d_model, cfg.rnn_width, cfg.conv_width)
        p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    elif kind == "W":
        p["tm"] = rw.timemix_init(ks[0], cfg.d_model, cfg.rwkv_head_dim)
        p["cm"] = rw.channelmix_init(ks[1], cfg.d_model, cfg.d_ff)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def _group_init(key, cfg: ArchConfig):
    pat = group_pattern(cfg)
    ks = jax.random.split(key, len(pat))
    return {f"b{j}": block_init(ks[j], kind, cfg) for j, kind in enumerate(pat)}


def lm_init(key, cfg: ArchConfig):
    n_groups, rem = group_counts(cfg)
    ks = jax.random.split(key, 5 + rem)
    params: Dict = {}
    params.update(embed_init(ks[0], cfg.padded_vocab, cfg.d_model))
    if cfg.frontend == "vision":
        params["patch_proj"] = dense_init(ks[1], (cfg.d_model, cfg.d_model))
    params["groups"] = jax.vmap(lambda k: _group_init(k, cfg))(
        jax.random.split(ks[2], n_groups)
    )
    pat = group_pattern(cfg)
    params["rem"] = [block_init(ks[5 + i], pat[i], cfg) for i in range(rem)]
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    params["lm_head"] = dense_init(ks[3], (cfg.d_model, cfg.padded_vocab), in_axis=0)
    return params


# ---------------------------------------------------------------- caches
def block_cache_init(kind: str, cfg: ArchConfig, batch: int, cap: int):
    """Decode-time cache for one block (no leading group dim)."""
    if kind == "A":
        shape = (batch, cap, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}
    if kind == "L":
        w = min(cfg.window_size or cap, cap)
        shape = (batch, w, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}
    if kind == "R":
        return rg.rglru_state_init(batch, cfg.rnn_width, cfg.conv_width)
    if kind == "W":
        return rw.rwkv_state_init(batch, cfg.d_model, cfg.rwkv_head_dim)
    raise ValueError(kind)


def lm_cache_init(cfg: ArchConfig, batch: int, cap: int):
    n_groups, rem = group_counts(cfg)
    pat = group_pattern(cfg)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), tree
        )

    groups = {f"b{j}": stack(block_cache_init(k, cfg, batch, cap)) for j, k in enumerate(pat)}
    rem_caches = [block_cache_init(pat[i], cfg, batch, cap) for i in range(rem)]
    return {"groups": groups, "rem": rem_caches}


# ---------------------------------------------------------------- blocks
def _ffn_apply(bp, cfg: ArchConfig, h2, ctx: ShardCtx):
    """Dense or MoE FFN on [B,S,D]; returns (out, aux)."""
    if cfg.num_experts:
        B, S, D = h2.shape
        kw = dict(n_experts=cfg.num_experts, top_k=cfg.moe_top_k,
                  capacity_factor=cfg.capacity_factor, ctx=ctx)
        use_smap = (
            ctx.mesh is not None
            and S % max(1, ctx.tp) == 0 and S >= ctx.tp
            and cfg.num_experts % max(1, ctx.tp) == 0
        )
        if use_smap:
            return moe_ffn_sharded(bp["moe"], h2, **kw)
        out, aux = moe_ffn(bp["moe"], h2.reshape(B * S, D), **kw)
        return out.reshape(B, S, D), aux
    return mlp(bp["ffn"], h2, ctx=ctx), jnp.zeros((), F32)


def _ring_positions(pos, cap: int):
    """Absolute position stored in each ring slot after writing at
    slot = pos % cap:  kpos[s] = pos - ((pos - s) mod cap); negative => empty."""
    s = jnp.arange(cap)
    return pos - jnp.mod(pos - s, cap)


def apply_block(
    bp, kind: str, h, *, cfg: ArchConfig, ctx: ShardCtx, positions,
    mode: str, cache=None, pos=None, chunk: int = 1024,
):
    """Returns (h, aux, new_cache)."""
    aux = jnp.zeros((), F32)
    new_cache = None
    window = cfg.window_size if kind == "L" else 0

    if kind in ("A", "L"):
        # Constrain the norm output to the seq-sharded layout so the
        # all-gather feeding QKV/MLP moves bf16, not the norm's f32 internals.
        hn = ctx.cstr(rmsnorm(bp["norm1"], h, cfg.norm_eps), "dp", "tp", None)
        if mode == "decode":
            B = h.shape[0]
            Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
            k_new = (hn @ bp["attn"]["wk"]).reshape(B, 1, Hkv, Dh)
            v_new = (hn @ bp["attn"]["wv"]).reshape(B, 1, Hkv, Dh)
            from .layers import rope as _rope
            k_new = _rope(k_new, positions, cfg.rope_theta)
            cap = cache["k"].shape[1]
            slot = jnp.mod(pos, cap) if kind == "L" else jnp.minimum(pos, cap - 1)
            k_buf = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
            v_buf = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
            k_buf = ctx.cstr(k_buf, "dp", "tp", None, None)
            v_buf = ctx.cstr(v_buf, "dp", "tp", None, None)
            kpos = _ring_positions(pos, cap) if kind == "L" else jnp.arange(cap)
            attn_out, _ = attention_block(
                bp["attn"], hn, cfg=cfg, positions=positions, causal=True,
                window=window, kv_override=(k_buf, v_buf, kpos), ctx=ctx, chunk=chunk,
            )
            new_cache = {"k": k_buf, "v": v_buf}
        else:
            attn_out, (k_full, v_full) = attention_block(
                bp["attn"], hn, cfg=cfg, positions=positions, causal=True,
                window=window, ctx=ctx, chunk=chunk,
            )
            if mode == "prefill":
                S = h.shape[1]
                if kind == "L":
                    w = min(cfg.window_size, S)
                    tail = jnp.arange(S - w, S)
                    slots = jnp.mod(tail, w)
                    k_ring = jnp.zeros_like(k_full[:, :w]).at[:, slots].set(k_full[:, S - w:])
                    v_ring = jnp.zeros_like(v_full[:, :w]).at[:, slots].set(v_full[:, S - w:])
                    new_cache = {"k": k_ring, "v": v_ring}
                else:
                    new_cache = {
                        "k": ctx.cstr(k_full, "dp", "tp", None, None),
                        "v": ctx.cstr(v_full, "dp", "tp", None, None),
                    }
        h = h + attn_out
        h = ctx.cstr(h, "dp", "tp", None)
        h2 = ctx.cstr(rmsnorm(bp["norm2"], h, cfg.norm_eps), "dp", "tp", None)
        ffn_out, aux = _ffn_apply(bp, cfg, h2, ctx)
        h = h + ffn_out

    elif kind == "R":
        hn = rmsnorm(bp["norm1"], h, cfg.norm_eps)
        state = cache if cache is not None else rg.rglru_state_init(h.shape[0], cfg.rnn_width, cfg.conv_width)
        out, new_state = rg.rglru_block_apply(bp["rglru"], hn, state, ctx=ctx)
        new_cache = new_state if mode in ("prefill", "decode") else None
        h = h + out
        h2 = rmsnorm(bp["norm2"], h, cfg.norm_eps)
        h = h + mlp(bp["ffn"], h2, ctx=ctx)

    elif kind == "W":
        B = h.shape[0]
        st = cache if cache is not None else rw.rwkv_state_init(B, cfg.d_model, cfg.rwkv_head_dim)
        hn = rmsnorm(bp["norm1"], h, cfg.norm_eps)
        tm_out, shift_tm, S_new = rw.timemix_apply(
            bp["tm"], hn, st["shift_tm"], st["S"], cfg.rwkv_head_dim, ctx=ctx
        )
        h = h + tm_out
        hn2 = rmsnorm(bp["norm2"], h, cfg.norm_eps)
        cm_out, shift_cm = rw.channelmix_apply(bp["cm"], hn2, st["shift_cm"])
        h = h + cm_out
        if mode in ("prefill", "decode"):
            new_cache = {"S": S_new, "shift_tm": shift_tm, "shift_cm": shift_cm}

    h = ctx.cstr(h, "dp", "tp", None)
    return h, aux, new_cache


# ---------------------------------------------------------------- forward
def _run_stack(params, h, *, cfg, ctx, positions, mode, caches=None, pos=None, chunk=1024):
    """Scan over groups + unrolled remainder. Returns (h, aux, new_caches)."""
    pat = group_pattern(cfg)
    n_groups, rem = group_counts(cfg)

    def group_body(carry, xs):
        h, aux = carry
        gp = xs[0] if caches is not None else xs
        gcache = xs[1] if caches is not None else None
        new_caches = {}
        for j, kind in enumerate(pat):
            bcache = gcache[f"b{j}"] if gcache is not None else None
            h, a, nc = apply_block(
                gp[f"b{j}"], kind, h, cfg=cfg, ctx=ctx, positions=positions,
                mode=mode, cache=bcache, pos=pos, chunk=chunk,
            )
            aux = aux + a
            if nc is not None:
                new_caches[f"b{j}"] = nc
        return (h, aux), (new_caches if new_caches else None)

    body = jax.checkpoint(group_body) if mode == "train" else group_body
    xs = params["groups"] if caches is None else (params["groups"], caches["groups"])
    (h, aux), group_caches_out = jax.lax.scan(body, (h, jnp.zeros((), F32)), xs)

    rem_caches_out = []
    for i in range(rem):
        bcache = caches["rem"][i] if caches is not None else None
        h, a, nc = apply_block(
            params["rem"][i], pat[i], h, cfg=cfg, ctx=ctx, positions=positions,
            mode=mode, cache=bcache, pos=pos, chunk=chunk,
        )
        aux = aux + a
        rem_caches_out.append(nc)

    out_caches = None
    if mode in ("prefill", "decode") and group_caches_out is not None:
        out_caches = {"groups": group_caches_out, "rem": rem_caches_out}
    return h, aux, out_caches


def _embed_input(params, batch, cfg: ArchConfig, ctx: ShardCtx):
    """Tokens (+ optional stub patch embeds) -> [B, S, D] + label info."""
    tok_h = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        patch_h = batch["patch_embeds"].astype(jnp.bfloat16) @ params["patch_proj"]
        h = jnp.concatenate([patch_h, tok_h], axis=1)
        text_offset = batch["patch_embeds"].shape[1]
    else:
        h, text_offset = tok_h, 0
    return ctx.cstr(h, "dp", "tp", None), text_offset


def lm_loss(params, batch, cfg: ArchConfig, ctx: ShardCtx = ShardCtx(), chunk: int = 1024):
    """Next-token loss. batch: {tokens [B,S_text] (+patch_embeds [B,P,D])}."""
    h, off = _embed_input(params, batch, cfg, ctx)
    positions = jnp.arange(h.shape[1])
    h, aux, _ = _run_stack(params, h, cfg=cfg, ctx=ctx, positions=positions,
                           mode="train", chunk=chunk)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    text_h = h[:, off:, :]
    labels = batch["tokens"][:, 1:]
    loss = chunked_lm_loss(params, text_h[:, :-1, :], labels, cfg.vocab_size, ctx=ctx)
    return loss + 0.01 * aux, {"loss": loss, "aux": aux}


def lm_hidden(params, batch, cfg: ArchConfig, ctx: ShardCtx = ShardCtx(), chunk: int = 1024):
    """Cache-free full-sequence forward. Returns (final-normed hidden states
    [B, S, D] at every position, decode caches)."""
    h, off = _embed_input(params, batch, cfg, ctx)
    positions = jnp.arange(h.shape[1])
    h, _, caches = _run_stack(params, h, cfg=cfg, ctx=ctx, positions=positions,
                              mode="prefill", chunk=chunk)
    return rmsnorm(params["final_norm"], h, cfg.norm_eps), caches


def lm_prefill(params, batch, cfg: ArchConfig, ctx: ShardCtx = ShardCtx(), chunk: int = 1024):
    """Full-sequence forward building decode caches. Returns (logits_last, caches)."""
    h, caches = lm_hidden(params, batch, cfg, ctx, chunk)
    logits = logits_head(params, h[:, -1:, :], cfg.vocab_size)
    return logits[:, 0, :], caches


def lm_decode(params, batch, cfg: ArchConfig, ctx: ShardCtx = ShardCtx()):
    """One decode step. batch: {token [B], pos scalar, caches}. Returns
    (logits [B, V], new_caches)."""
    tok = batch["token"]
    pos = batch["pos"]
    caches = batch["caches"]
    h = params["embed"][tok][:, None, :].astype(jnp.bfloat16)
    positions = jnp.full((1,), pos, jnp.int32)
    h, _, new_caches = _run_stack(params, h, cfg=cfg, ctx=ctx, positions=positions,
                                  mode="decode", caches=caches, pos=pos)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = logits_head(params, h[:, 0, :], cfg.vocab_size)
    return logits, new_caches
