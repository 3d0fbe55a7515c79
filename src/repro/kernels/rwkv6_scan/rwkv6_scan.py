"""Pallas TPU chunked WKV6 kernel (data-dependent decay linear attention).

Grid (B*H, T/CT) with the time axis sequential; the [N, N] state lives in
VMEM scratch across chunk iterations.  Within a chunk the recurrence is
evaluated in matmul form on the MXU:

    L_t   = cumsum(log w)           (per key channel)
    A_ts  = (r_t e^{L_{t-1}}) . (k_s e^{-L_s}),  s < t   (strictly lower)
    out_t = A @ v + (r_t . u k_t) v_t + (r_t e^{L_{t-1}}) @ S
    S'    = diag(e^{L_CT}) S + (k e^{L_CT - L})^T @ v

Numerics: the chunk is processed in SUB-chunks of 16 steps with exact local
log-space exponents — no clamping.  Within 16 steps, |cumsum(log w)| stays
inside f32's exp range for any w >= ~0.003 (per-step decay of 99.7%); below
that, a channel's cross-step contribution is < 0.3% of scale and underflows
harmlessly to 0.  The exact-scan oracle (ref.py) bounds the error in tests,
including a strong-decay case.

VMEM per program (CT=128, N=64): chunks 4 x CT x N f32 = 128 KiB, per-sub
A (16 x 16), S (N x N) 16 KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16  # sub-chunk length: exactness window for strong decays


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, chunk: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)            # [1, N]
    n = u.shape[-1]
    ti = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    lower = si < ti
    # Inclusive prefix sum over the sub-chunk as a matmul with a triangle of
    # ones (the TPU lowering has no cumsum); HIGHEST keeps it f32-exact.
    incl = (si <= ti).astype(jnp.float32)

    def sub_body(i, S):
        rows = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        rs = r_ref[0, rows, :].astype(jnp.float32)     # [SUB, N]
        ks = k_ref[0, rows, :].astype(jnp.float32)
        vs = v_ref[0, rows, :].astype(jnp.float32)
        lw = jnp.log(jnp.maximum(w_ref[0, rows, :].astype(jnp.float32), 1e-38))
        L = jax.lax.dot_general(incl, lw, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        Lprev = L - lw
        a = rs * jnp.exp(Lprev)
        b = ks * jnp.exp(-L)
        A = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        A = jnp.where(lower, A, 0.0)
        intra = jax.lax.dot_general(A, vs, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        diag = jnp.sum(rs * u * ks, axis=-1, keepdims=True) * vs
        inter = jax.lax.dot_general(a, S, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        o_ref[0, rows, :] = (intra + diag + inter).astype(o_ref.dtype)
        l_last = L[-1:, :]
        kdec = ks * jnp.exp(l_last - L)
        return jnp.exp(l_last).T * S + jax.lax.dot_general(
            kdec, vs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    s_ref[...] = jax.lax.fori_loop(0, chunk // SUB, sub_body, s_ref[...])


def wkv6_pallas(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: [B, T, H, N]; u: [H, N] -> out [B, T, H, N] (f32)."""
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    # flatten (B, H) into the grid's parallel axis; time is sequential
    def flat(a):
        return jnp.moveaxis(a, 2, 1).reshape(B * H, T, N)

    rf, kf, vf, wf = (flat(a) for a in (r, k, v, w))
    # u as (H, 1, N): a (1, N) block is the full last two dims, as the TPU
    # tiling requires; program bh = b * H + h reads head h's row.
    uf = u.reshape(H, 1, N)
    grid = (B * H, T // chunk)
    try:
        cparams = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    except (AttributeError, TypeError):
        cparams = pltpu.TPUCompilerParams(dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, 1, N), lambda bh, it: (bh % H, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, N), lambda bh, it: (bh, it, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=cparams,
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return jnp.moveaxis(out.reshape(B, H, T, N), 1, 2)
