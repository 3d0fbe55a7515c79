"""Serving batch plane benchmark: single-scan batched drain vs per-request loop.

Drives ``CacheAffinityRouter`` through a round-based virtual-time serving
harness — each round completes the previous wave (``complete_batch``),
enqueues a burst of Zipf prefix-reuse requests, and runs one ``tick`` — in
three modes over the byte-identical call sequence:

  * ``looped``   — ``batch_drain=False`` + the reference dispatcher: the
    incumbent per-request ``notify()`` loop (one full window scan and one
    tier-promotion pass per decision);
  * ``loop_vec`` — ``batch_drain=False`` + the vectorized dispatcher
    (attribution row: array scoring without the batched drain);
  * ``batched``  — ``batch_drain=True`` + the vectorized dispatcher: every
    free replica drained from one ``notify_batch`` window scan against a
    frozen presence snapshot, tier promotions applied as a per-batch delta,
    and misses admitted through one batched ``TransferEngine`` resolution.

Every row *asserts* the decision-parity escape hatch: the three modes must
produce bit-identical assignment logs, and looped vs batched must end with
identical per-replica tier contents.  Divergence raises -> ERROR row -> the
``run.py --smoke`` gate and CI fail (the same contract as
``bench_dispatch_vec`` / ``bench_index_scale``).

The headline rows run max-cache-hit — the *delaying* policy, where the
looped path re-scans the affinity-delayed backlog on every decision and the
batched drain amortizes all of it into one scan (>= 3x requests/sec at
batch=32 at full scale).  Two companion rows keep the other planes honest:
a tight-HBM stream whose hits constantly promote from the host tier
(exercising the deferred promote/demote delta log) and a good-cache-compute
stream with cold arrivals (exercising the batched admission path).  Under
GCC the batch-entry snapshot would diverge from the looped path's evolving
view once the replication cap binds mid-burst; the router therefore runs
the batched drain with admission emulation (the dispatcher overlays the
batch's own assignments over the frozen snapshot), and a dedicated
cap-bound row (``gcc_capbound_b32``) asserts the drain stays bit-exact
while the cap binds — emulated branches are counted in
``batch_emulated_decisions`` and residual replay divergences in
``stale_snapshot_drops`` (asserted zero there: never silent).

A final row leaves the model for the physical plane: real bf16 KV pages
under a ``RealPayload`` backend are demoted to host memory by HBM pressure
and ``jax.device_put`` back on access, so ``measured_swapin`` reports the
*measured* (wall-clock, block-until-ready) dram->hbm swap-in bandwidth next
to the machine-model roofline — raising (-> ERROR row) on byte corruption
or a measured bandwidth >10x the roofline (an unblocked async copy).

Writes ``BENCH_serve.json`` with an appended ``history`` entry per run
(including the measured swap-in bandwidth).
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, "src")
    sys.path.insert(0, "benchmarks")
    from bench_util import append_history, zipf_sessions
else:
    from .bench_util import append_history, zipf_sessions

from repro.diffusion.tiers import TierSpec
from repro.runtime.router import CacheAffinityRouter, RoutedRequest

BLOCK = 2.0 * 1024**2

MODES = {
    "looped": (False, "reference"),
    "loop_vec": (False, "vectorized"),
    "batched": (True, "vectorized"),
}


def build_router(policy: str, batch_drain: bool, impl: str, replicas: int,
                 hbm_blocks: int, dram_blocks: int, window: int,
                 max_object_replicas: int, obs=None) -> CacheAffinityRouter:
    router = CacheAffinityRouter(
        policy=policy,
        window=window,
        max_object_replicas=max_object_replicas,
        object_size_fn=lambda obj: BLOCK,
        tier_specs=[TierSpec("hbm", hbm_blocks * BLOCK),
                    TierSpec("dram", dram_blocks * BLOCK, 64e9)],
        persistent_bw_bytes_per_s=4e9,
        nic_bw_bytes_per_s=16e9,
        batch_drain=batch_drain,
        dispatcher_impl=impl,
        log_assignments=True,
        obs=obs,
    )
    for _ in range(replicas):
        router.add_replica()
    return router


def drive(router: CacheAffinityRouter, sids: List[int], batch: int,
          blocks: int, decode_s: float = 0.004) -> int:
    """Round-based serving pump (virtual time): complete the previous wave
    as one batch, enqueue this round's burst, drain once.  Identical call
    sequence for every mode — only the router's drain strategy differs."""
    t = 1000.0
    served = 0
    rid = 0
    i = 0
    wave: List = []
    stall = 0
    while i < len(sids) or router.queue_length() > 0 or wave:
        before = served
        finished = [rr for a in wave for rr in a.requests]
        served += len(finished)
        nxt = list(router.complete_batch(finished, now=t)) if finished else []
        burst = sids[i:i + batch]
        i += len(burst)
        for sid in burst:
            objs = tuple(f"kv:s{sid}:b{b}" for b in range(blocks))
            router.enqueue(RoutedRequest(rid, objs, submit_time_s=t), now=t)
            rid += 1
        nxt.extend(router.tick(t))
        wave = nxt
        t += decode_s
        stall = stall + 1 if served == before and not wave else 0
        if stall > 3:
            break               # policy refuses the remainder
    return served


def _contents(router: CacheAffinityRouter) -> Dict[str, Dict[str, str]]:
    return {name: store.tiers.contents()
            for name, store in router.stores.items()}


def run_case(label: str, policy: str, batch: int, blocks: int,
             hbm_blocks: int, dram_blocks: int, sessions: int, replicas: int,
             n: int, alpha: float = 1.0, window: int = 512,
             max_object_replicas: Optional[int] = None,
             reps: int = 1) -> Dict[str, float]:
    if max_object_replicas is None:
        max_object_replicas = 2 * replicas   # headroom: cap never binds
    results = {}
    for mode, (batch_drain, impl) in MODES.items():
        best = None
        for _ in range(max(1, reps)):
            # Best-of-reps with a fresh router per rep: allocator/GC jitter
            # swings a single run by ~1.5x; the run is deterministic, so
            # the logs must agree across reps (asserted) and min wall time
            # is the measurement.
            router = build_router(policy, batch_drain, impl, replicas,
                                  hbm_blocks, dram_blocks, window,
                                  max_object_replicas)
            drive(router, list(range(sessions)), 1, blocks)  # warm sessions
            sids = zipf_sessions(n, sessions, alpha, seed=7)
            t0 = time.perf_counter()
            served = drive(router, sids, batch, blocks)
            wall = time.perf_counter() - t0
            if best is not None and best["log"] != router.assignment_log:
                raise RuntimeError(
                    f"serve_batch[{label}]: non-deterministic assignment "
                    f"log across repetitions of the {mode} drive")
            if best is None or served / wall > best["rps"]:
                best = {
                    "log": router.assignment_log,
                    "rps": served / max(wall, 1e-9),
                    "served": served,
                    "router": router,
                }
        results[mode] = best
    ref, bat = results["looped"], results["batched"]
    for mode in ("loop_vec", "batched"):
        if results[mode]["log"] != ref["log"]:
            a, b = ref["log"], results[mode]["log"]
            d = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     min(len(a), len(b)))
            raise RuntimeError(
                f"serve_batch[{label}]: {mode} drain diverged from the "
                f"per-request loop at decision {d}: "
                f"looped={a[d:d + 3]} {mode}={b[d:d + 3]}")
    if _contents(ref["router"]) != _contents(bat["router"]):
        raise RuntimeError(
            f"serve_batch[{label}]: batched drain left different tier "
            f"contents than the per-request loop")
    if batch >= 32 and bat["rps"] < results["loop_vec"]["rps"]:
        # The whole point of the single-scan drain is amortization: at
        # batch sizes that give it anything to amortize it must beat the
        # per-request loop over the same vectorized engine, or the batch
        # plane has regressed (as the lazy per-item argmax repair once did).
        raise RuntimeError(
            f"serve_batch[{label}]: batched drain ({bat['rps']:.0f} rps) "
            f"lost to the looped-vectorized path "
            f"({results['loop_vec']['rps']:.0f} rps) at batch={batch}")
    # Pool-wide tier counters come from the snapshot() protocol (the same
    # aggregate the metrics registry publishes as ``tiers.*``) — the bench
    # no longer hand-picks dataclass fields per store.
    tiers = bat["router"]._tiers_snapshot()
    engine = bat["router"].engine
    return {
        "looped_rps": ref["rps"],
        "loop_vec_rps": results["loop_vec"]["rps"],
        "batched_rps": bat["rps"],
        "speedup": bat["rps"] / max(ref["rps"], 1e-9),
        "served": ref["served"],
        "hit_rate": bat["router"].stats.hit_rate,
        "promotions": tiers["promotions"],
        "deferred_applied": tiers["deferred_applied"],
        "batch_drains": bat["router"].dispatcher.stats.batch_drains,
        "shared_flights": engine.stats.shared if engine else 0,
        "batch_emulated":
            bat["router"].dispatcher.stats.batch_emulated_decisions,
        "stale_drops": bat["router"].stats.stale_snapshot_drops,
    }


def measured_swapin_case(pages: int = 8, page_mib: float = 4.0,
                         laps: int = 3) -> Dict[str, float]:
    """Real-payload plane: actual KV pages cycled through HBM pressure.

    A 2-page HBM tier over a host-DRAM tier, ``pages`` bf16 pages resident:
    every access to a demoted page is a *measured* swap-in (device_put +
    block_until_ready), every HBM eviction a measured demotion.  Returns
    the dram->hbm aggregate; raises on byte corruption or a measured
    bandwidth >10x the machine-model roofline.
    """
    import numpy as np
    import jax.numpy as jnp
    from repro.diffusion.payload import RealPayload
    from repro.diffusion.tiers import TieredStore, TierSpec

    backend = RealPayload("serve")
    store = TieredStore(
        "r0", [TierSpec("hbm", 2.0), TierSpec("dram", float(pages), 50e9)],
        payload=backend)
    rng = np.random.default_rng(0)
    page_elems = int(page_mib * 1024**2) // 2        # bf16
    originals = {}
    for i in range(pages):
        obj = f"kv:p{i}"
        host = rng.standard_normal(page_elems).astype(np.float32)
        originals[obj] = np.asarray(jnp.asarray(host, jnp.bfloat16))
        store.admit(obj, 1.0)
        backend.put(obj, jnp.asarray(originals[obj]),
                    store.tier_of(obj) or store.top_tier)
    for _ in range(laps):
        for obj in originals:            # demoted pages swap back in, timed
            store.access(obj)
    bad = [obj for obj, host in originals.items()
           if not np.array_equal(np.asarray(backend.get(obj)), host)]
    if bad:
        raise RuntimeError(
            f"serve_batch[measured_swapin]: KV pages corrupted by the "
            f"demote/swap-in cycle: {bad}")
    violations = backend.measured.check_roofline(factor=10.0)
    if violations:
        raise RuntimeError(
            f"serve_batch[measured_swapin]: {violations}")
    edges = {f"{r['src']}->{r['dst']}": r for r in backend.measured.rows()}
    swap = edges.get("dram->hbm")
    roofline = backend.measured.tier_roofline()
    if swap is None or swap["moves"] == 0:
        raise RuntimeError(
            "serve_batch[measured_swapin]: no dram->hbm swap-in was "
            "measured (payload plane not engaged)")
    return {
        "gbps": swap["bytes_per_s"] / 1e9,
        "roofline_gbps": min(roofline("dram"), roofline("hbm")) / 1e9,
        "moves": swap["moves"],
        "bytes": swap["bytes"],
        "us_per_move": 1e6 * swap["seconds"] / swap["moves"],
        "demote_gbps": edges["hbm->dram"]["bytes_per_s"] / 1e9
        if "hbm->dram" in edges else 0.0,
    }


def obs_case(n: int, reps: int = 3) -> Dict[str, float]:
    """Observability plane contract: parity and the <=5% overhead budget.

    Assertions, all raising (-> ERROR row) on violation:

      * *span parity* — the looped reference drain and the single-scan
        batched drain, driven over the byte-identical seeded stream with
        tracing on, must emit the same causal request/dispatch/transfer
        span structure per request (``TraceBuffer.parity_digest``).  The
        batched path finalizes dispatch spans only after stale-snapshot
        replay, so a digest mismatch means the trace is lying about what
        the router decided;
      * *attribution parity* — one level up: the critical-path analyzer's
        per-request wall-time decomposition (queue/dispatch/promote/
        transfer/service) and the aggregated blame table must be identical
        over both drains' traces.  Guaranteed only in zero-stale-conversion
        regimes, so ``stale_snapshot_drops == 0`` is asserted first;
      * *overhead* — the obs-enabled batched drain (analyzer registered,
        SLO board live) must hold >= 0.95x the rps of the obs-disabled run
        (best-of-``reps`` each, position-rotated), and must make
        bit-identical decisions (observation never steers).  A deficit
        only fails when it exceeds the measurement's own resolution (half
        the off-side spread) — see the inline comment.  A
        ``trace_sample=8`` run must drop structural spans
        (deterministically fewer recorded, parity digest unchanged)
        without narrowing the overhead margin.
    """
    from repro.obs import CriticalPathAnalyzer, Observability, SLOSpec

    # Live SLOs ride the obs-enabled runs so the completion hook's cost is
    # inside the overhead measurement.  Virtual-time latencies here are
    # multiples of the 4ms decode step; 50ms keeps the latency objective
    # healthy while the hit-rate board sees real good/bad traffic.
    slos = (SLOSpec("p99_latency", "latency", target=0.99, threshold_s=0.050),
            SLOSpec("hit_rate", "hit_rate", target=0.50))

    def mkobs(sample: int = 1) -> "Observability":
        return Observability(trace_sample=sample, slo_specs=slos)

    def run(batch_drain: bool, impl: str, obs, n_req: int = n) -> Dict[str, float]:
        router = build_router("max-cache-hit", batch_drain, impl,
                              replicas=16, hbm_blocks=12, dram_blocks=24,
                              window=512, max_object_replicas=32, obs=obs)
        drive(router, list(range(64)), 1, blocks=2)       # warm sessions
        sids = zipf_sessions(n_req, 64, 1.0, seed=7)
        t0 = time.perf_counter()
        served = drive(router, sids, 32, blocks=2)
        wall = time.perf_counter() - t0
        return {"rps": served / max(wall, 1e-9), "served": served,
                "log": router.assignment_log, "router": router}

    # --- span parity: looped reference vs batched drain, tracing on.
    obs_ref, obs_bat = mkobs(), mkobs()
    ref = run(False, "reference", obs_ref)
    bat = run(True, "vectorized", obs_bat)
    if ref["log"] != bat["log"]:
        raise RuntimeError("serve_batch[obs]: decision parity broke with "
                           "tracing enabled")
    dig_ref = obs_ref.trace.parity_digest()
    dig_bat = obs_bat.trace.parity_digest()
    if not dig_ref or obs_ref.trace.total == 0:
        raise RuntimeError("serve_batch[obs]: tracing enabled but no spans "
                           "were recorded")
    if dig_ref != dig_bat:
        bad = next(rid for rid in sorted(set(dig_ref) | set(dig_bat))
                   if dig_ref.get(rid) != dig_bat.get(rid))
        raise RuntimeError(
            f"serve_batch[obs]: span parity diverged at request {bad}: "
            f"looped={dig_ref.get(bad)} batched={dig_bat.get(bad)}")
    # --- attribution parity: the wall-time blame derived from those spans.
    if bat["router"].stats.stale_snapshot_drops:
        raise RuntimeError(
            "serve_batch[obs]: stale-snapshot conversions on the seeded "
            "stream — attribution parity precondition broken")
    ana_ref = CriticalPathAnalyzer(obs_ref.trace)
    ana_bat = CriticalPathAnalyzer(obs_bat.trace)
    att_ref, att_bat = ana_ref.attribution_digest(), ana_bat.attribution_digest()
    if att_ref != att_bat:
        bad = next(rid for rid in sorted(set(att_ref) | set(att_bat))
                   if att_ref.get(rid) != att_bat.get(rid))
        raise RuntimeError(
            f"serve_batch[obs]: critical-path attribution diverged at "
            f"request {bad}: looped={att_ref.get(bad)} "
            f"batched={att_bat.get(bad)}")
    blame_ref, blame = ana_ref.blame_table(), ana_bat.blame_table()
    if blame_ref != blame:
        raise RuntimeError(
            f"serve_batch[obs]: blame tables diverged looped-vs-batched: "
            f"{blame_ref} != {blame}")
    # SLO determinism across drain modes: same latencies -> same counts.
    slo_ref = obs_ref.slo.snapshot()
    slo_bat = obs_bat.slo.snapshot()
    if slo_ref != slo_bat:
        raise RuntimeError(
            f"serve_batch[obs]: SLO boards diverged looped-vs-batched: "
            f"{slo_ref} != {slo_bat}")
    # --- structural-span sampling (trace_sample=8): deterministically
    # fewer spans recorded, parity digest untouched.
    obs_s = mkobs(sample=8)
    run(True, "vectorized", obs_s)
    if obs_s.trace.snapshot()["sampled_out"] <= 0:
        raise RuntimeError("serve_batch[obs]: trace_sample=8 sampled "
                           "nothing out (no structural spans offered?)")
    if obs_s.trace.total >= obs_bat.trace.total:
        raise RuntimeError(
            f"serve_batch[obs]: sampled trace recorded {obs_s.trace.total} "
            f"spans, not fewer than the unsampled {obs_bat.trace.total}")
    if obs_s.trace.parity_digest() != dig_bat:
        raise RuntimeError("serve_batch[obs]: structural sampling changed "
                           "the parity digest (request spans were dropped)")
    # --- overhead: obs-off vs obs-on vs obs-on-sampled batched drains.
    # Measured at a fixed >=3000-request scale regardless of the parity
    # scale: the hooks cost O(1) per request, so a longer drain states the
    # same contract with usable signal-to-noise — a 300-request drain
    # (~60ms) measures the container's scheduler jitter (+-15%), not the
    # plane's ~2-4% cost.  The three variants rotate position within each
    # rep (a cgroup CPU quota favors whoever runs right after a refill) and
    # each side keeps its best rep; a failing first measurement is re-taken
    # once at higher reps before it counts.  Because this box's run-to-run
    # jitter can exceed the 5% budget itself, a residual deficit only
    # *fails* when it is resolvable: it must exceed half the off-side's own
    # observed spread — an injected regression (>=20%) clears that bar in
    # any weather, a throttling window does not.
    n_ov = max(n, 3000)
    kinds = ("off", "on", "sam")
    factories = {"off": lambda: None, "on": mkobs, "sam": lambda: mkobs(8)}
    samples: Dict[str, List[float]] = {k: [] for k in kinds}

    def measure(k: int) -> None:
        for rep in range(max(1, k)):
            rot = rep % 3
            got: Dict[str, Dict[str, float]] = {}
            for kind in kinds[rot:] + kinds[:rot]:
                got[kind] = run(True, "vectorized", factories[kind](), n_ov)
            if got["off"]["log"] != got["on"]["log"] \
                    or got["off"]["log"] != got["sam"]["log"]:
                raise RuntimeError("serve_batch[obs]: observability changed "
                                   "the drain's decisions")
            for kind in kinds:
                samples[kind].append(got[kind]["rps"])

    def ratios() -> Tuple[float, float]:
        off = max(samples["off"])
        return (max(samples["on"]) / max(off, 1e-9),
                max(samples["sam"]) / max(off, 1e-9))

    measure(reps)
    ratio, ratio_s = ratios()
    if ratio < 0.95 or ratio_s + 0.05 < ratio:
        measure(2 * reps + 1)
        ratio, ratio_s = ratios()
    # Measurement resolution: the spread of the obs-off runs themselves.
    jitter = ((max(samples["off"]) - min(samples["off"]))
              / max(max(samples["off"]), 1e-9))
    if ratio < 0.95 and (0.95 - ratio) >= 0.5 * jitter:
        raise RuntimeError(
            f"serve_batch[obs]: obs-enabled drain holds only {ratio:.1%} "
            f"of the obs-disabled rps (best {max(samples['on']):.0f} vs "
            f"{max(samples['off']):.0f}, off-side jitter {jitter:.1%}) — "
            f"the observability plane blew its 5% overhead budget")
    # Margin check: thinning structural spans removes work, so the sampled
    # ratio must track the unsampled one (the *work* reduction itself is
    # asserted deterministically above; wall clock gets the same
    # resolvability bar).
    if ratio_s + 0.05 < ratio and (ratio - ratio_s - 0.05) >= 0.5 * jitter:
        raise RuntimeError(
            f"serve_batch[obs]: sampling structural spans 1-in-8 narrowed "
            f"the overhead margin ({ratio_s:.1%} vs {ratio:.1%} unsampled)")
    crit_frac = {seg: round(blame[seg]["frac"], 4)
                 for seg in blame if blame[seg]["frac"] > 0.0}
    slo_snap = obs_bat.slo.snapshot()
    return {
        "spans": float(obs_bat.trace.total),
        "traced_requests": float(len(dig_bat)),
        "rps_off": max(samples["off"]),
        "rps_on": max(samples["on"]),
        "overhead_pct": 100.0 * (1.0 - ratio),
        "overhead_sampled_pct": 100.0 * (1.0 - ratio_s),
        "sampled_out": obs_s.trace.snapshot()["sampled_out"],
        "crit_frac": crit_frac,
        "slo_firing": ",".join(obs_bat.slo.firing()) or "none",
        "slo_budget_p99": slo_snap["p99_latency.budget_remaining"],
        "slo_budget_hit_rate": slo_snap["hit_rate.budget_remaining"],
        "hit_rate_live": obs_bat.collect_all().get("router.hit_rate", 0.0),
        "perf_index_live":
            obs_bat.collect_all().get("perf.performance_index", 0.0),
    }


def main(n: int = 3000, seed: int = 0) -> List[Tuple[str, float, str]]:
    n = max(300, n)
    reps = 1 if n <= 1000 else 2     # smoke stays fast; full scale de-jitters
    rows: List[Tuple[str, float, str]] = []
    batch32: Dict[str, float] = {}
    # Headline: the delaying policy under affinity backlog, batch-size sweep.
    for batch in (1, 8, 32, 128):
        m = run_case(f"mch_b{batch}", "max-cache-hit", batch, blocks=3,
                     hbm_blocks=12, dram_blocks=24, sessions=96, replicas=32,
                     n=n, reps=reps)
        if batch == 32:
            batch32 = m
        rows.append((
            f"serve_batch/mch_b{batch}",
            1e6 / max(m["batched_rps"], 1e-9),
            f"looped_rps={m['looped_rps']:.0f};"
            f"loop_vec_rps={m['loop_vec_rps']:.0f};"
            f"batched_rps={m['batched_rps']:.0f};"
            f"speedup={m['speedup']:.2f};equal=True;"
            f"hit_rate={m['hit_rate']:.2f};served={int(m['served'])}",
        ))
    # Deferred-promotion plane: tight HBM, every hit swaps in from the host
    # tier, the batch applies the coalesced promote delta per drain.
    m = run_case("promote_b32", "max-cache-hit", 32, blocks=1, hbm_blocks=2,
                 dram_blocks=16, sessions=96, replicas=32, n=n)
    rows.append((
        "serve_batch/promote_b32",
        1e6 / max(m["batched_rps"], 1e-9),
        f"speedup={m['speedup']:.2f};equal=True;"
        f"promotions={int(m['promotions'])};"
        f"deferred_applied={int(m['deferred_applied'])}",
    ))
    # Batched-admission plane: GCC with replication headroom + cold arrivals
    # exercising one-pass union resolution through the transfer engine.
    m = run_case("gcc_admit_b32", "good-cache-compute", 32, blocks=1,
                 hbm_blocks=2, dram_blocks=16, sessions=max(96, n // 6),
                 replicas=32, n=n)
    rows.append((
        "serve_batch/gcc_admit_b32",
        1e6 / max(m["batched_rps"], 1e-9),
        f"speedup={m['speedup']:.2f};equal=True;"
        f"hit_rate={m['hit_rate']:.2f};"
        f"shared_flights={int(m['shared_flights'])}",
    ))
    # Replication-cap-bound plane: the cap binds mid-burst, so the frozen
    # snapshot alone would duplicate hot objects past the cap; admission
    # emulation replays the looped path's evolving view and the drain must
    # stay bit-exact.  Capacity is generous (no eviction cascades), so any
    # residual replay divergence would be a counting bug: assert zero.
    m = run_case("gcc_capbound_b32", "good-cache-compute", 32, blocks=1,
                 hbm_blocks=64, dram_blocks=64, sessions=max(96, n // 6),
                 replicas=32, n=n, max_object_replicas=2)
    if m["stale_drops"]:
        raise RuntimeError(
            f"serve_batch[gcc_capbound_b32]: {int(m['stale_drops'])} "
            f"uncounted-at-dispatch parity divergences leaked into the "
            f"replay (expected zero with no eviction cascades)")
    rows.append((
        "serve_batch/gcc_capbound_b32",
        1e6 / max(m["batched_rps"], 1e-9),
        f"speedup={m['speedup']:.2f};equal=True;"
        f"hit_rate={m['hit_rate']:.2f};"
        f"emulated={int(m['batch_emulated'])};"
        f"stale_drops={int(m['stale_drops'])}",
    ))
    # Observability plane: span + attribution parity looped-vs-batched,
    # the 5% overhead contract (obs-enabled rps >= 0.95x obs-disabled,
    # SLO board live, asserted), and structural-span sampling.
    ob = obs_case(min(n, 1500))
    crit = ";".join(f"crit_{seg}={frac:.2f}"
                    for seg, frac in sorted(ob["crit_frac"].items()))
    rows.append((
        "serve_batch/obs_plane",
        1e6 / max(ob["rps_on"], 1e-9),
        f"span_parity=True;attribution_parity=True;"
        f"spans={int(ob['spans'])};"
        f"traced_requests={int(ob['traced_requests'])};"
        f"overhead_pct={ob['overhead_pct']:.1f};"
        f"overhead_sampled_pct={ob['overhead_sampled_pct']:.1f};"
        f"sampled_out={int(ob['sampled_out'])};"
        f"rps_on={ob['rps_on']:.0f};rps_off={ob['rps_off']:.0f};"
        f"{crit};slo_firing={ob['slo_firing']};"
        f"slo_budget_p99={ob['slo_budget_p99']:.2f};"
        f"live_hit_rate={ob['hit_rate_live']:.2f};"
        f"live_perf_index={ob['perf_index_live']:.3g}",
    ))
    # Physical plane: measured (not modeled) swap-in bandwidth — real bf16
    # KV pages demoted by HBM pressure and device_put back on access.
    sw = measured_swapin_case()
    rows.append((
        "serve_batch/measured_swapin",
        sw["us_per_move"],
        f"measured_gbps={sw['gbps']:.3f};"
        f"roofline_gbps={sw['roofline_gbps']:.1f};"
        f"moves={int(sw['moves'])};bytes={int(sw['bytes'])};"
        f"demote_gbps={sw['demote_gbps']:.3f};byte_equal=True",
    ))
    if batch32:
        append_history("BENCH_serve.json", {
            "config": {"policy": "max-cache-hit", "batch": 32, "blocks": 3,
                       "replicas": 32, "window": 512, "requests": n},
            "looped_rps": round(batch32["looped_rps"], 1),
            "loop_vec_rps": round(batch32["loop_vec_rps"], 1),
            "batched_rps": round(batch32["batched_rps"], 1),
            "speedup": round(batch32["speedup"], 2),
            "equal": True,
            "measured_swapin_gbps": round(sw["gbps"], 3),
            "measured_swapin_roofline_gbps": round(sw["roofline_gbps"], 1),
            "obs_overhead_pct": round(ob["overhead_pct"], 2),
            "obs_overhead_sampled_pct": round(ob["overhead_sampled_pct"], 2),
            "obs_spans": int(ob["spans"]),
            "crit_frac": ob["crit_frac"],
            "slo": {"firing": ob["slo_firing"],
                    "budget_p99": round(ob["slo_budget_p99"], 4),
                    "budget_hit_rate": round(ob["slo_budget_hit_rate"], 4)},
        })
    return rows


if __name__ == "__main__":
    for row in main():
        print(",".join(map(str, row)))
