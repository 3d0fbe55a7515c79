"""Mixture-of-Experts FFN: capacity-based dispatch, two execution paths.

``moe_ffn`` (GSPMD, mesh-free): argsort-ranked scatter into a global
[E, C, D] buffer + batched expert GEMMs.  Used for smoke tests, decode steps
(tiny T), and single-device runs.

``moe_ffn_sharded`` (shard_map, production): row x column expert parallelism.
Tokens stay on their data-parallel row (all-gathered over 'tp' at entry, like
any column-parallel FFN); experts are sharded over the 'tp' axis.  Each
device dispatches its row's tokens to ITS local experts (local argsort-ranked
scatter — no global [T*K, D] materialization, which is what OOMed the pure
GSPMD lowering at qwen3 scale: 537 GiB/device), runs the grouped GEMMs
(TPU-target realization: kernels/moe_gmm), and the partial outputs
psum-scatter back to the seq-sharded residual.  Capacity drops fall through
the residual (GShard semantics).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import F32, dense_init
from .sharding import ShardCtx


def moe_init(key, d_model: int, d_ff: int, n_experts: int):
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d_model, n_experts), dtype=jnp.float32),
        "experts": {
            "w1": dense_init(ks[1], (n_experts, d_model, d_ff)),   # gate proj
            "w3": dense_init(ks[2], (n_experts, d_model, d_ff)),   # up proj
            "w2": dense_init(ks[3], (n_experts, d_ff, d_model)),   # down proj
        },
    }


def capacity(T: int, top_k: int, n_experts: int, factor: float, multiple: int = 8) -> int:
    c = int(math.ceil(T * top_k / n_experts * factor))
    return max(multiple, ((c + multiple - 1) // multiple) * multiple)


def _rank_positions(flat_e: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Stable rank of each entry within its bucket (argsort + searchsorted)."""
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(tk) - first
    return jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))


def _router(p, x2d, top_k: int):
    logits = x2d.astype(F32) @ p["router"].astype(F32)             # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)              # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _aux_loss(probs, gate_idx, n_experts: int):
    tk = gate_idx.size
    f_e = jnp.zeros((n_experts,), F32).at[gate_idx.reshape(-1)].add(1.0) / tk
    return n_experts * jnp.sum(f_e * probs.mean(axis=0))


def _expert_mlp(w, buf):
    """buf: [E, C, D] -> [E, C, D] through SwiGLU experts (grouped GEMM)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w["w1"], preferred_element_type=F32)
    u = jnp.einsum("ecd,edf->ecf", buf, w["w3"], preferred_element_type=F32)
    h = (jax.nn.silu(g) * u).astype(buf.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w["w2"], preferred_element_type=F32).astype(buf.dtype)


# ------------------------------------------------------- GSPMD / local path
def moe_ffn(p, x2d, *, n_experts: int, top_k: int, capacity_factor: float,
            ctx: ShardCtx = ShardCtx()) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x2d: [T, D] -> ([T, D], aux). Plain-jnp path (small T / no mesh)."""
    T, D = x2d.shape
    E, K = n_experts, top_k
    C = capacity(T, K, E, capacity_factor)
    probs, gate_vals, gate_idx = _router(p, x2d, K)
    aux = _aux_loss(probs, gate_idx, E)

    flat_e = gate_idx.reshape(T * K)
    pos = _rank_positions(flat_e, E)
    keep = pos < C
    slot = jnp.clip(pos, 0, C - 1)

    buf = jnp.zeros((E, C, D), x2d.dtype)
    out = jnp.zeros((T, D), F32)
    for k in range(K):  # k-sliced scatters cap the transient at [T, D]
        ek, sk = flat_e[k::K], slot[k::K]
        keepk = keep[k::K]
        buf = buf.at[ek, sk].add(jnp.where(keepk[:, None], x2d, 0))
    buf = ctx.cstr(buf, "tp", "dp", None)
    y = _expert_mlp(p["experts"], buf)
    for k in range(K):
        ek, sk = flat_e[k::K], slot[k::K]
        w = (gate_vals[:, k] * keep[k::K]).astype(F32)
        out = out + y[ek, sk].astype(F32) * w[:, None]
    return out.astype(x2d.dtype), aux


# -------------------------------------------------- shard_map EP (production)
def moe_ffn_sharded(p, x, *, n_experts: int, top_k: int, capacity_factor: float,
                    ctx: ShardCtx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] global (residual seq-sharded on tp). Returns ([B,S,D], aux).

    Row x column EP: device (i, j) processes dp-row i's tokens for tp-column
    j's experts; partial outputs reduce back via psum_scatter over 'tp'.
    """
    mesh = ctx.mesh
    E, K = n_experts, top_k
    tp = ctx.tp_axis
    tp_size = ctx.tp
    assert E % tp_size == 0, (E, tp_size)
    E_loc = E // tp_size
    dp_spec = ctx._resolve("dp", x.shape[0])

    def inner(xl, router_w, w1, w3, w2):
        B_loc, S, D = xl.shape
        T = B_loc * S
        x2 = xl.reshape(T, D)
        probs, gate_vals, gate_idx = _router({"router": router_w}, x2, K)
        aux = _aux_loss(probs, gate_idx, E)
        aux = jax.lax.pmean(aux, tp)
        if dp_spec is not None:
            aux = jax.lax.pmean(aux, dp_spec)

        j = jax.lax.axis_index(tp)
        e_lo = j * E_loc
        local = (gate_idx >= e_lo) & (gate_idx < e_lo + E_loc)          # [T, K]
        C = capacity(T, K, E, capacity_factor)
        # Rank only local assignments; non-local entries go to bucket E_loc.
        flat_e = jnp.where(local, gate_idx - e_lo, E_loc).reshape(T * K)
        pos = _rank_positions(flat_e, E_loc + 1)
        keep = (flat_e < E_loc) & (pos < C)
        # Dropped / non-local entries route to overflow slot C of a C+1-wide
        # buffer (sliced off before the GEMM) — no masked [T, D] copies.
        slot = jnp.where(keep, jnp.clip(pos, 0, C - 1), C)
        eid = jnp.clip(flat_e, 0, E_loc - 1)

        buf = jnp.zeros((E_loc, C + 1, D), x2.dtype)
        for k in range(K):
            buf = buf.at[eid[k::K], slot[k::K]].add(x2)
        y = _expert_mlp({"w1": w1, "w3": w3, "w2": w2}, buf[:, :C])
        out = jnp.zeros((T, D), x2.dtype)
        for k in range(K):
            w = (gate_vals[:, k] * keep[k::K]).astype(x2.dtype)
            yk = y[eid[k::K], jnp.clip(slot[k::K], 0, C - 1)]
            out = out + yk * w[:, None]
        out = out.reshape(B_loc, S, D).astype(xl.dtype)
        # Partial sums over expert columns -> seq-sharded residual.
        out = jax.lax.psum_scatter(out, tp, scatter_dimension=1, tiled=True)
        return out, aux

    in_specs = (
        P(dp_spec, None, None),          # x: row tokens, full seq, full D
        P(None, None),                   # router (replicated)
        P(tp, None, None),               # w1 [E(tp), D, F]
        P(tp, None, None),               # w3
        P(tp, None, None),               # w2 [E(tp), F, D]
    )
    out_specs = (P(dp_spec, tp, None), P())
    smap = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    w = p["experts"]
    return smap(x, p["router"], w["w1"], w["w3"], w["w2"])
