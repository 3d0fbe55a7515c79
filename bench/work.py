"""Operations and bytes the served algorithm needs, from shapes alone.

A count here is what the algorithm requires, whatever implements it, so a
batched, fused or sparse implementation can raise a share of the roofline
but never read it above 100%:

* weights are read once per call, in the type they are served in (bf16;
  the MoE router is float32);
* an MoE layer needs only the experts its tokens were routed to: ``top_k``
  of them per layer.  For a call of several tokens that is a lower bound
  (they may share experts), so the share stays a lower bound too;
* the embedding table costs one row per token, the LM head one product per
  position whose next token is wanted (every decode token; the last prompt
  position of a prefill);
* attention reads the keys and values of the positions each token attends,
  not the whole cache it was given.

``m`` is any object with the architecture's sizes as attributes
(``d_model``, ``num_layers``, ``num_heads``, ``num_kv_heads``, ``head_dim``,
``d_ff``, ``vocab_size``, ``num_experts``, ``moe_top_k``).

A work counts module.  These counts are for a uniform stack of
grouped-query attention and SwiGLU FFNs or experts.  A configuration file
whose model counts otherwise names its own module (``"work": "<path under
bench/ without .py>"``; this file where it names none), which the harness
loads by path and hands to the metric readers as ``run.work``.  Such a
module provides ``decode_call(m, contexts)`` and ``prefill_call(m,
prompt_len)``, each returning ``(FLOPs, bytes)`` with the meaning given
below, counted by the rules above: what the model's algorithm needs, never
what one implementation of it happens to do.  ``m`` is the program's
configuration.  The chip's peaks (``peaks``) and the least time
(``needed_seconds``) stay here, in one copy.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Sequence, Tuple

BF16 = 2
F32 = 4
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; a kind not in the table raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add them with their source")
    return table[device_kind]


def _layer_matmul_params(m) -> Tuple[int, int]:
    """(bf16 matmul parameters one token uses in one layer, f32 router
    parameters)."""
    d = m.d_model
    attn = 2 * d * m.num_heads * m.head_dim + 2 * d * m.num_kv_heads * m.head_dim
    experts = m.moe_top_k if m.num_experts else 1
    ffn = experts * 3 * d * m.d_ff
    router = d * m.num_experts if m.num_experts else 0
    return attn + ffn, router


def _weight_bytes(m) -> int:
    """Bytes of the weights one call needs (lm head included, embedding
    excluded: it is counted by the row)."""
    bf16, router = _layer_matmul_params(m)
    norms = (2 * m.num_layers + 1) * m.d_model
    return (m.num_layers * (bf16 * BF16 + router * F32) + norms * BF16
            + m.d_model * m.vocab_size * BF16)


def _kv_bytes_per_position(m) -> int:
    return m.num_layers * 2 * m.num_kv_heads * m.head_dim * BF16


def _attn_flops(m, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` (query, key) pairs."""
    return m.num_layers * 4 * m.num_heads * m.head_dim * pairs


def decode_call(m, contexts: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode call; ``contexts`` holds, for each token
    decoded in the call, the positions it attends (itself included)."""
    t = len(contexts)
    bf16, router = _layer_matmul_params(m)
    per_token = m.num_layers * (bf16 + router) + m.d_model * m.vocab_size
    flops = 2 * t * per_token + _attn_flops(m, sum(contexts))
    kv = _kv_bytes_per_position(m)
    nbytes = (_weight_bytes(m) + t * m.d_model * BF16
              + sum(contexts) * kv + t * kv)
    return float(flops), float(nbytes)


def prefill_call(m, prompt_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill of ``prompt_len`` tokens, causal, with
    the next token's logits at the last position only."""
    s = prompt_len
    bf16, router = _layer_matmul_params(m)
    flops = (2 * s * m.num_layers * (bf16 + router) + 2 * m.d_model * m.vocab_size
             + _attn_flops(m, s * (s + 1) // 2))
    nbytes = (_weight_bytes(m) + s * m.d_model * BF16
              + s * _kv_bytes_per_position(m))
    return float(flops), float(nbytes)


def needed_seconds(flops: float, nbytes: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_bf16"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
