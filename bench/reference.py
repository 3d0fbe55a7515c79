"""Plain reference of the served decoder LMs, and the seeded weights both
sides run on.

Independent of the program: this file imports nothing of it.  It reads the
parameter tree by its key names (``embed``, ``groups/b0/{norm1, attn, norm2,
ffn | moe}``, ``final_norm``, ``lm_head``), layer-stacked on the leading
axis, and computes in float32 with every product at HIGHEST precision:
RMSNorm, rotary positions (rotate-half), causal grouped-query attention, a
SwiGLU FFN or a top-k mixture of SwiGLU experts with no token dropped.
Layers run one at a time under ``lax.scan``, so only one layer's weights
are widened to float32 at once.

``mode="fp8"`` is the control: the same forward with both operands of every
product rounded to float8 (e4m3, one absmax scale per tensor), the step
below the bf16 the configurations serve in.

A reference module.  A configuration file names the module that holds its
model's forward (``"reference": "<path under bench/ without .py>"``; this
file where it names none), and the harness loads it by path.  Such a module
provides:

* ``FIELDS``: the program configuration's attributes it reads.  The harness
  hands them to ``hidden`` as the dict ``m``, with the file's
  ``semantics`` added;
* ``hidden(params, tokens, m, mode)``: the final-normed hidden states
  ``[B, S, D]`` in float32 of a cache-free causal forward over ``tokens``
  ``[B, S]``, with every product taken from ``reference._products(mode)``,
  so that the fp8 control rounds its products too.  ``block`` below is one
  layer of the uniform stack, for a module whose stack mixes it with others.

What a module shares stays here, in one copy: the seeded weights
(``make_weights``, ``seed_key``), the head (``_logits``) and the served
tokens' gaps (``served_gaps``, which runs the module's ``hidden``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


# ----------------------------------------------------------------- weights
def seed_key(seed: int) -> jax.Array:
    """A key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _leaf_scale(path: Tuple[str, ...], shape: Tuple[int, ...]) -> float:
    if path[-1] == "embed":
        return 1.0 / math.sqrt(shape[-1])
    return 1.0 / math.sqrt(shape[-2])


def make_weights(shapes: Any, seed: int) -> Any:
    """Random weights for a tree of ``ShapeDtypeStruct``s, in one jitted
    call on the default device: normal over sqrt(fan-in) for matrices,
    1 + N(0, 0.1) for norm scales, N(0, 0.1) for other vectors (a bias),
    each leaf in its own dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for k, path, s in zip(keys, paths, specs):
            z = jax.random.normal(k, s.shape, F32)
            if path[-1] == "scale":
                out.append((1.0 + 0.1 * z).astype(s.dtype))
            elif len(s.shape) == 1:
                out.append((0.1 * z).astype(s.dtype))
            else:
                out.append((z * _leaf_scale(path, s.shape)).astype(s.dtype))
        return out

    leaves = jax.jit(gen)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ----------------------------------------------------------------- forward
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(F32) * s


def _products(mode: str):
    """(matmul, einsum) at the mode's precision."""
    if mode == "f32":
        return (lambda a, b: jnp.matmul(a, b, precision=HIGHEST),
                lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST))
    if mode == "fp8":
        return (lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST),
                lambda eq, a, b: jnp.einsum(eq, _fp8(a), _fp8(b),
                                            precision=HIGHEST))
    raise ValueError(f"unknown reference mode {mode!r}")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, H, D]; rotate-half rotary positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], F32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(lp, x, m, mm, es):
    b, s, _ = x.shape
    h, g, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    w = {k: v.astype(F32) for k, v in lp.items()}
    # query head i reads key/value head i // (h // g)
    q = _rope(mm(x, w["wq"]).reshape(b, s, h, dh),
              m["rope_theta"]).reshape(b, s, g, h // g, dh)
    k = _rope(mm(x, w["wk"]).reshape(b, s, g, dh), m["rope_theta"])
    v = mm(x, w["wv"]).reshape(b, s, g, dh)
    scores = es("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(dh)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = es("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
    return mm(out.reshape(b, s, h * dh), w["wo"])


def _swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def _moe(lp, x, m, mm):
    """Every expert over every token, weighted by the token's top-k gates
    (zero elsewhere): what a dropless top-k layer computes."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    probs = jax.nn.softmax(mm(t, lp["router"].astype(F32)), -1)
    top, idx = jax.lax.top_k(probs, m["moe_top_k"])
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(t.shape[0])[:, None], idx].set(top)
    ex = lp["experts"]

    def one(acc, e):
        w1, w3, w2, g = e
        y = _swiglu(t, w1.astype(F32), w3.astype(F32), w2.astype(F32), mm)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(t),
                          (ex["w1"], ex["w3"], ex["w2"], gates.T))
    return out.reshape(b, s, d)


# The program configuration's attributes that ``hidden`` reads.
FIELDS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "vocab_size",
          "rope_theta", "norm_eps", "moe_top_k")


def block(x, lp, m: Dict[str, Any], mm, es):
    """One pre-norm layer of the stack: attention, then a SwiGLU FFN or a
    mixture of experts; ``mm, es`` are ``_products(mode)``."""
    eps = m["norm_eps"]
    a = _rmsnorm(x, lp["norm1"]["scale"].astype(F32), eps)
    x = x + _attention(lp["attn"], a, m, mm, es)
    f = _rmsnorm(x, lp["norm2"]["scale"].astype(F32), eps)
    if "moe" in lp:
        return x + _moe(lp["moe"], f, m, mm)
    w = {k: v.astype(F32) for k, v in lp["ffn"].items()}
    return x + _swiglu(f, w["w_gate"], w["w_up"], w["w_down"], mm)


def hidden(params, tokens, m: Dict[str, Any], mode: str = "f32"):
    """Final-normed hidden states [B, S, D] of a cache-free causal forward
    over ``tokens`` [B, S]."""
    if params["rem"] or set(params["groups"]) != {"b0"}:
        raise ValueError("reference: only uniform attention stacks")
    mm, es = _products(mode)
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lp: (block(x, lp, m, mm, es), None), x,
                        params["groups"]["b0"])
    return _rmsnorm(x, params["final_norm"]["scale"].astype(F32),
                    m["norm_eps"])


def _logits(params, h, rows, m, mode):
    """Logits over the real vocabulary at ``rows`` [B, P] of ``h``."""
    mm, _ = _products(mode)
    picked = jnp.take_along_axis(h, rows[..., None], axis=1)
    head = params["lm_head"][:, :m["vocab_size"]].astype(F32)
    return mm(picked, head)


@functools.partial(jax.jit, static_argnames=("mkey", "control", "forward"))
def _gaps(params, tokens, rows, served, mkey, control, forward):
    m = dict(mkey)
    ref = _logits(params, forward(params, tokens, m, "f32"), rows, m, "f32")
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    if not control:
        return gap, None
    low = _logits(params, forward(params, tokens, m, "fp8"), rows, m, "fp8")
    pick = jnp.argmax(low, -1)
    return gap, best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]


def served_gaps(params, tokens, rows, served, m: Dict[str, Any],
                control: bool = False, forward: Callable = hidden):
    """For each sequence of ``tokens`` [B, S] and each of its ``rows``
    [B, P]: how far the reference's logit of the token served after that
    row (``served`` [B, P]) lies below the reference's best, and with
    ``control`` the same for the token the fp8 forward puts first.
    ``forward`` is a reference module's ``hidden``.  Returns numpy arrays
    [B, P] (the control's is None without it)."""
    mkey = tuple(sorted(m.items()))
    gap, low = _gaps(params, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(rows, jnp.int32),
                     jnp.asarray(served, jnp.int32), mkey, bool(control),
                     forward)
    return (np.asarray(gap, np.float32),
            None if low is None else np.asarray(low, np.float32))
