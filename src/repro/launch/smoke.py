"""Smoke phases of the served path, driven through ``DiffusionServer``'s own
entry points (``submit()`` / ``step()``, as ``launch.serve`` drives it).

* ``serve_smoke`` — two replicas behind the cache-affinity router with a
  host-DRAM tier and the real payload plane: HBM evictions demote the KV
  tensors to host memory and later hits swap them back onto the device.
  Every request is checked against a cache-free reference: the full
  forward (``lm_hidden``, the prefill path) over exactly the tokens the
  session's cache holds.
* ``placement_smoke`` — a fixed pool of replicas spread one per device,
  against the same pool all on the first device, on one seeded stream.

``chip_smoke.py`` runs both at full width on a TPU; the tier-1 tests run
them at reduced size on the CPU.  Both raise ``SmokeFailure`` on any miss.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.api import attn_chunk
from ..models.layers import logits_head
from ..models.lm import lm_hidden
from ..runtime.serve_loop import DiffusionServer, Request

# Cached decode vs cache-free forward, both bf16 end to end: the two paths
# reduce in different orders, so the last logits may differ by this share
# of the reference's largest |logit|, and a greedy token may differ from the
# reference's argmax only where it scores within that margin of the best.
REL_TOL = 5e-2


class SmokeFailure(AssertionError):
    """A smoke check missed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds the backend spent compiling since ``start()`` (JAX's
    ``/jax/core/compile/backend_compile_duration`` events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.count += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def session_stream(sessions: int, requests: int, seed: int) -> List[str]:
    """Two passes over every session in order (the second pass finds each
    prefix demoted to host DRAM when HBM slots are fewer than sessions),
    then seeded picks."""
    sids = [f"s{i}" for i in range(sessions)]
    rng = np.random.default_rng(seed)
    stream = (sids * 2)[:requests]
    while len(stream) < requests:
        stream.append(sids[int(rng.integers(0, sessions))])
    return stream


def _drive(srv: DiffusionServer, prompts: Dict[str, np.ndarray],
           stream: List[str], new_tokens: int) -> List[Request]:
    reqs = []
    for sid in stream:
        reqs.append(srv.submit(sid, prompts[sid], max_new_tokens=new_tokens))
        srv.step()
    return reqs


def _lost(srv: DiffusionServer) -> int:
    return len(srv.router._requests) + srv.router.queue_length()


def _tokens(req: Request) -> np.ndarray:
    """The request's greedy tokens, read from the device after the fact."""
    return np.concatenate([np.asarray(t) for t in req.generated])


def make_reference(cfg: ArchConfig, length: int, width: int) -> Callable:
    """Jitted cache-free forward over ``length`` tokens; returns the logits
    at positions n-width .. n-1 for a traced ``n`` (one compile)."""

    def ref(params, tokens, n):
        h, _ = lm_hidden(params, {"tokens": tokens}, cfg,
                         chunk=attn_chunk(length))
        rows = jax.lax.dynamic_slice_in_dim(h, n - width, width, axis=1)
        return logits_head(params, rows, cfg.vocab_size)[0, :, :cfg.vocab_size]

    return jax.jit(ref)


def check_against_reference(srv: DiffusionServer, reqs: List[Request],
                            prompts: Dict[str, np.ndarray], new_tokens: int,
                            log: Callable[[str], None]) -> Dict[str, float]:
    """Hold every request's cached decode to the cache-free forward.

    The decode loop feeds ``prompt[-1]`` at the session's position and then
    its own greedy tokens; a prefix hit continues the state the same
    replica left, so the session's cache holds, per (replica, session):
    the prompt, then for each request ``prompt[-1]`` and all but the last
    generated token.  The reference runs over exactly those tokens."""
    V, cap = srv.cfg.vocab_size, srv.cap
    ref_fn = make_reference(srv.cfg, cap, new_tokens)
    held: Dict[Tuple[str, str], List[int]] = {}
    worst, exact, near, total = 0.0, 0, 0, 0
    for req in reqs:
        key = (req.replica, req.session_id)
        prompt = [int(t) % V for t in prompts[req.session_id]]
        _check(not req.prefix_hit or key in held,
               f"request {req.request_id}: hit on {key} with no state served")
        seq = held[key] if req.prefix_hit else list(prompt)
        gen = _tokens(req)
        _check(len(gen) == new_tokens,
               f"request {req.request_id}: {len(gen)} of {new_tokens} tokens")
        seq = seq + [prompt[-1]] + [int(t) for t in gen[:-1]]
        held[key] = seq
        n = len(seq)
        _check(n <= cap, f"request {req.request_id}: {n} tokens > cap {cap}")
        tokens = np.zeros((1, cap), np.int32)
        tokens[0, :n] = seq
        ref = np.asarray(ref_fn(srv.params, jnp.asarray(tokens),
                                jnp.asarray(n, jnp.int32)), np.float32)
        last = np.asarray(req.last_logits, np.float32)[0, :V]
        scale = float(np.abs(ref[-1]).max())
        err = float(np.abs(last - ref[-1]).max()) / scale
        worst = max(worst, err)
        _check(err <= REL_TOL,
               f"request {req.request_id} ({req.session_id} on {req.replica},"
               f" {n} tokens): last logits off the reference by {err:.4f} of"
               f" max|logit| (tolerance {REL_TOL})")
        for i, tok in enumerate(gen):
            row = ref[i]
            margin = REL_TOL * float(np.abs(row).max())
            total += 1
            if tok == int(row.argmax()):
                exact += 1
            elif row[tok] >= row.max() - margin:
                near += 1
            else:
                raise SmokeFailure(
                    f"request {req.request_id} step {i}: greedy token {tok} "
                    f"scores {row[tok]:.4f}, reference argmax "
                    f"{int(row.argmax())} scores {row.max():.4f}")
    log(f"reference: requests={len(reqs)} tokens_checked={total} "
        f"greedy_exact={exact} greedy_within_tol={near} "
        f"max_last_logit_err={worst:.6f} (of max|logit|; tolerance "
        f"{REL_TOL}) -> passed")
    return {"ref_max_err": worst, "greedy_exact": exact,
            "greedy_near": near, "tokens_checked": total}


def _prompts(cfg: ArchConfig, sessions: int, prompt_len: int,
             seed: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    return {f"s{i}": rng.integers(0, cfg.vocab_size, size=(prompt_len,))
            for i in range(sessions)}


def _peak_bytes(device: Any) -> Optional[int]:
    stats = device.memory_stats() if hasattr(device, "memory_stats") else None
    return None if not stats else stats.get("peak_bytes_in_use")


def describe(cfg: ArchConfig, log: Callable[[str], None]) -> None:
    devs = jax.devices()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"params={cfg.param_count()}")


def serve_smoke(cfg: ArchConfig, *, seed: int = 0, replicas: int = 2,
                sessions: int = 6, requests: int = 16, max_sessions: int = 2,
                host_cache_sessions: int = 8, prompt_len: int = 512,
                cache_cap: int = 1024, new_tokens: int = 16,
                log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Serve a seeded stream on a fixed replica pool and check it."""
    describe(cfg, log)
    prompts = _prompts(cfg, sessions, prompt_len, seed)
    stream = session_stream(sessions, requests, seed)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        srv = DiffusionServer(cfg, policy="good-cache-compute",
                              max_replicas=replicas, min_replicas=replicas,
                              cache_cap=cache_cap, max_sessions=max_sessions,
                              host_cache_sessions=host_cache_sessions,
                              payload="real", seed=seed)
        jax.block_until_ready(srv.params)
        build_s = time.perf_counter() - t0
        reqs = _drive(srv, prompts, stream, new_tokens)
        jax.block_until_ready([r.last_logits for r in reqs])
    s = srv.stats
    lost = _lost(srv)
    log(f"build_s={build_s:.3f} (params from seed {seed}) "
        f"compile_s={clock.seconds:.3f} over {clock.count} programs")
    log(f"serve: submitted={len(reqs)} served={s.served} lost={lost} "
        f"prefix_hits={s.prefix_hits} swap_ins={s.swap_ins} "
        f"staged_demotions={srv.staged_demotion_share():.0%} "
        f"prefills={s.prefills} decode_steps={s.decode_steps} "
        f"replicas={len(srv.replicas)}")
    log("smoke timings (per-request wall ms, compile included in the first "
        "of each shape; not metrics): "
        + " ".join(f"{r.response_time_s * 1e3:.1f}" for r in reqs))
    swap = srv.measured.bandwidth("dram", "hbm")
    log(f"swap-in dram->hbm: {swap / 1e9:.3f} GB/s measured "
        f"(roofline violations: {srv.measured.check_roofline()})")
    _check(s.served == len(reqs) and lost == 0,
           f"served {s.served} of {len(reqs)} submitted, {lost} lost")
    _check(s.prefix_hits > 0, "no prefix hits")
    _check(s.swap_ins > 0, "no swap-ins from the host-DRAM tier")
    _check(srv.measured.check_roofline() == [],
           f"impossible bandwidth: {srv.measured.check_roofline()}")
    with CompileClock() as ref_clock:
        ref = check_against_reference(srv, reqs, prompts, new_tokens, log)
    peak = _peak_bytes(jax.devices()[0])
    log(f"reference compile_s={ref_clock.seconds:.3f}; "
        f"peak_bytes_in_use={peak if peak is not None else 'not reported'}")
    return {"served": s.served, "submitted": len(reqs), "lost": lost,
            "prefix_hits": s.prefix_hits, "swap_ins": s.swap_ins,
            "compile_s": clock.seconds, "peak_bytes_in_use": peak, **ref}


def _devices_of(tree: Any) -> set:
    out: set = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        out |= set(leaf.devices())
    return out


def _placement(srv: DiffusionServer) -> Dict[str, Any]:
    """Replica -> its one device, checked through every array it holds:
    parameters and the KV of each session resident in its HBM."""
    placed = {}
    for name, rep in srv.replicas.items():
        _check(_devices_of(rep.params) == {rep.device},
               f"{name}: parameters on {_devices_of(rep.params)}, "
               f"replica on {rep.device}")
        backend = srv.router.stores[name].tiers.payload
        kv_devices: set = set()
        for sid in rep.sessions:
            obj = f"kv:{sid}"
            if backend.tier_of(obj) == "hbm":
                kv_devices |= _devices_of(backend.value(obj))
        _check(kv_devices <= {rep.device},
               f"{name}: KV on {kv_devices}, replica on {rep.device}")
        placed[name] = rep.device
    return placed


def placement_smoke(cfg: ArchConfig, *, seed: int = 0, replicas: int = 4,
                    sessions: int = 8, requests: int = 16,
                    max_sessions: int = 1, host_cache_sessions: int = 8,
                    prompt_len: int = 512, cache_cap: int = 1024,
                    new_tokens: int = 8,
                    log: Callable[[str], None] = print) -> Dict[str, Any]:
    """One replica per device vs all on device 0, same seeded stream:
    placement must be one-to-one and the served results identical."""
    describe(cfg, log)
    devices = jax.local_devices()
    _check(len(devices) == replicas,
           f"{replicas} replicas need {replicas} devices, found {len(devices)}")
    prompts = _prompts(cfg, sessions, prompt_len, seed)
    stream = session_stream(sessions, requests, seed)
    runs = {}
    for label, devs in (("spread", None), ("device0", [devices[0]])):
        with CompileClock() as clock:
            srv = DiffusionServer(cfg, policy="good-cache-compute",
                                  max_replicas=replicas,
                                  min_replicas=replicas, cache_cap=cache_cap,
                                  max_sessions=max_sessions,
                                  host_cache_sessions=host_cache_sessions,
                                  payload="real", seed=seed, devices=devs)
            reqs = _drive(srv, prompts, stream, new_tokens)
            results = [(r.replica, r.prefix_hit, _tokens(r)) for r in reqs]
        placed = _placement(srv)
        s = srv.stats
        log(f"{label}: served={s.served} lost={_lost(srv)} "
            f"prefix_hits={s.prefix_hits} swap_ins={s.swap_ins} "
            f"compile_s={clock.seconds:.3f} placement="
            + ",".join(f"{n}:{d.id}" for n, d in sorted(placed.items())))
        _check(s.served == len(reqs) and _lost(srv) == 0,
               f"{label}: served {s.served} of {len(reqs)}")
        runs[label] = (placed, results)
        del srv, reqs
        gc.collect()
    spread, _ = runs["spread"]
    _check(sorted(d.id for d in spread.values())
           == sorted(d.id for d in devices),
           f"spread run: replicas on {[d.id for d in spread.values()]}, "
           f"expected one on each of {[d.id for d in devices]}")
    _check(set(runs["device0"][0].values()) == {devices[0]},
           "device0 run: replicas left device 0")
    a, b = runs["spread"][1], runs["device0"][1]
    for i, ((ra, ha, ta), (rb, hb, tb)) in enumerate(zip(a, b)):
        _check(ra == rb and ha == hb,
               f"request {i}: spread ({ra}, hit={ha}) vs device0 "
               f"({rb}, hit={hb})")
        _check(np.array_equal(ta, tb),
               f"request {i}: tokens differ {ta.tolist()} vs {tb.tolist()}")
    hits = sum(h for _, h, _ in a)
    log(f"placement: {len(a)} requests, identical assignments, hits "
        f"({hits}) and tokens across both runs -> passed")
    return {"requests": len(a), "hits": hits,
            "devices": sorted(d.id for d in spread.values())}
