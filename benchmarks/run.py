"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Default scale runs the DES
experiments at 25K tasks (minutes); ``--full`` reproduces the paper's 250K
(the EXPERIMENTS.md numbers).  ``--quick`` drops to 6K for CI.

Bench modules are imported *lazily*, one per suite, at the moment the suite
runs: importing this module (or starting a ``--smoke`` / ``--only`` run)
must not pay for the JAX-heavy benches (roofline/model-error pull in the
launch/model stack), so the smoke gate starts in a couple of seconds on a
bare CPU install and an import-time failure in one bench degrades to that
suite's ERROR row instead of killing the whole harness.  The harness still
runs every suite, then exits nonzero if any printed an ERROR row.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper scale (250K tasks)")
    ap.add_argument("--quick", action="store_true", help="CI scale (6K tasks)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny iterations: exercises every suite end-to-end "
                         "in ~a minute so benchmark scripts can't silently rot")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--check-regressions", action="store_true",
                    help="run the bench regression sentinel over the "
                         "BENCH_*.json histories instead of any suite; "
                         "exits nonzero when a declared metric regressed "
                         "beyond its noise-scaled threshold")
    ap.add_argument("--regress-report", default="",
                    help="with --check-regressions: also write the markdown "
                         "report to this path")
    args = ap.parse_args()
    if args.check_regressions:
        from repro.obs.regress import main as regress_main
        argv = ["--report", args.regress_report] if args.regress_report else []
        sys.exit(regress_main(argv))
    if args.smoke:
        n, n_model, n_sched, n_serve, n_scale = 1_000, 300, 1_000, 300, 1_000
        n_idx = 300
    else:
        n = 250_000 if args.full else (6_000 if args.quick else 25_000)
        n_model = 20_000 if args.full else (2_000 if args.quick else 6_000)
        n_sched = 250_000 if args.full else (6_000 if args.quick else 25_000)
        n_serve = 1_000 if args.quick else 4_000
        n_scale = 40_000 if args.full else 8_000
        n_idx = 2_000 if args.quick else (8_000 if args.full else 4_000)

    # (suite name, module, main() argument) — module import deferred to run
    # time.  The serve_batch / dispatch_vec / index_scale suites *assert*
    # decision parity (batched-vs-looped serving drain, vectorized-vs-
    # reference dispatch, sharded-vs-flat index); any divergence raises ->
    # ERROR row -> the smoke gate (CI) fails.
    suites = [
        ("scheduler", "bench_scheduler", n_sched),
        ("serve_routing", "bench_serve_routing", n_serve),
        ("serve_batch", "bench_serve_batch", n_serve),
        # Robustness plane: kills 25% of the replica pool mid-Zipf-stream
        # and asserts zero lost requests, 1:1 DRP back-fill, bounded
        # hit-rate recovery, and availability-SLO budget intact — plus the
        # attached-but-idle chaos plane staying bit-identical to no plane.
        ("chaos", "bench_chaos", n_serve),
        # Overload robustness plane: four Zipf tenants (one 3x hog) with
        # distinct SLOs under chaos arrival spikes — asserts credit-ordered
        # shedding, light-tenant p99-within-SLO, exact shed/reject/serve
        # accounting, per-store tenant tier quotas, and the attached-but-
        # idle controller staying bit-identical to admission=None.
        ("admission", "bench_admission", n_serve),
        ("diffusion_tiers", "bench_diffusion_tiers", n_serve),
        ("dispatch_vec", "bench_dispatch_vec", n_idx),
        ("index_scale", "bench_index_scale", n_idx),
        ("provisioning", "bench_provisioning", n),
        ("cache_throughput", "bench_cache_throughput", n),
        ("pi_speedup", "bench_pi_speedup", n),
        ("model_error", "bench_model_error", n_model),
        ("scale", "bench_scale", n_scale),
        ("roofline", "bench_roofline", None),
        # Real KV bytes through every physical home: raises (-> ERROR row)
        # on byte mismatch after the HBM->DRAM->disk->HBM tour or on a
        # measured bandwidth >10x the machine-model roofline (an unblocked
        # async copy).  Writes the measured-bandwidth history
        # (BENCH_payload.json, uploaded with the other BENCH_* artifacts).
        ("payload_roundtrip", "bench_payload", None),
    ]
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod_name, arg in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f".{mod_name}", __package__)
            rows = mod.main() if arg is None else mod.main(arg)
            for row in rows:
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# suite {name} took {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"# {len(failed)} suite(s) failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
