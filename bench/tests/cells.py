"""Benchmark cells cut to a size the CPU runs in seconds, for the tests:
a small model, a short cache and short prompts; server settings and the
mix's shape as committed."""

from __future__ import annotations

import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402


# A small mixture of experts with the published 64 experts and top-8: with
# ``ArchConfig.reduced``'s 4 experts and top-2, one expert chosen otherwise
# in bf16 than in float32 moves a token's output by half, and the widest gap
# of a sound run is as wide as the control's.
SMALL_MOE = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
                 head_dim=64, d_ff=64, vocab_size=512, num_experts=64,
                 moe_top_k=8, capacity_factor=8.0, rope_theta=10_000.0)


def small_cell(name: str):
    """The cell ``name`` at a size the CPU runs in seconds."""
    from repro.configs import get_arch
    cell = run.load_cell(name)
    conf = cell["config"]
    full = get_arch(conf["model"])
    if full.num_experts:
        conf["overrides"] = dict(SMALL_MOE)
    else:
        small = full.reduced()
        conf["overrides"] = {
            f.name: getattr(small, f.name) for f in dataclasses.fields(small)
            if getattr(small, f.name) != getattr(full, f.name)}
    conf["sizes"] = {}
    conf["server"] = dict(conf["server"], cache_cap=96)
    conf["check"] = {"requests": 4, "batch": 4}
    cell["mix"] = dict(cell["mix"], prompt_lens=[16, 32], new_tokens=[2, 6],
                       knee_req_s=20.0, trace_s=0.5)
    return cell
