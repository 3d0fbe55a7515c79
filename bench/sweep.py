"""Find a cell's knee on the chip: the highest steady rate it sustains.

    python bench/sweep.py --workload <name> --seconds <s> \\
        --seeds 11 12 --rates 1.0 1.5 2.0 2.5 [--limit-ms 2500]

One process: the weights are made once, from the first seed; each rate and
each seed gets a fresh server, the mix's warm-up and a window of
``--seconds`` at that constant rate (the mix's shape otherwise, its
``drain`` included).  Every rate runs on the same seeds, so the rates differ
only in their arrival times.  Each point prints one JSON line: offered and
served requests and tokens per second (over the whole window, as it
closes: ``bench/metrics/tokens_per_s.py``), the requests queued
at the close, the median and 90th-percentile latency, and the median
latency of the window's last third over its first third (above 1.5 the
backlog grows).

The rule, fixed before a sweep: a point is sustained when it serves at
least 95% of what it is offered, with no growing backlog and, with
``--limit-ms``, a 90th percentile under that limit.  A rate is sustained
when every seed's point is.  The knee is the highest rate at which it and
every lower rate swept are sustained; it is written by hand into the mix's
``knee_req_s``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def point(cell, cfg, weights, devices, rate: float, seed: int,
          seconds: float) -> dict:
    mix = dict(cell["mix"], knee_req_s=rate,
               phases=[{"seconds": 1.0, "x_knee": 1.0}])
    srv = run.build_server(cfg, weights, cell["config"]["server"], devices)
    sched = run.schedule(mix, seed, seconds, cfg.vocab_size,
                         int(cell["config"]["server"]["cache_cap"]))
    spans = run.Spans()
    run.warm_up(srv, sched, spans)
    rec = run.run_window(srv, sched, seconds, spans, drain=bool(mix["drain"]))
    t0 = rec["t0"]
    lat = [(s.turn.due_s, s.done - t0 - s.turn.due_s) for s in rec["served"]]
    lat.sort()
    third = max(1, len(lat) // 3)
    first = np.median([x for _, x in lat[:third]])
    last = np.median([x for _, x in lat[-third:]])
    values = [x for _, x in lat]
    tokens = sum(s.turn.new_tokens for s in rec["served"])
    out = {"rate_req_s": rate, "seed": seed, "due": len(sched.window),
           "offered_req_s": len(sched.window) / seconds,
           "served_req_s": len(rec["served"]) / (rec["t1"] - t0),
           "tokens_per_s": tokens / (rec["t1"] - t0),
           "queued": len(rec["queued_due_s"]), "lost": len(rec["lost"]),
           "window_s": rec["t1"] - t0,
           "p50_ms": float(np.percentile(values, 50)) * 1e3,
           "p90_ms": float(np.percentile(values, 90)) * 1e3,
           "backlog_growth": float(last / first),
           "compiles": rec["compiles"]}
    del srv, rec
    gc.collect()
    return out


def sustained(got: dict, limit_ms: float) -> bool:
    return (got["served_req_s"] >= 0.95 * got["offered_req_s"]
            and got["lost"] == 0 and got["backlog_growth"] < 1.5
            and got["p90_ms"] <= limit_ms)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--limit-ms", type=float, default=float("inf"))
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.use_compile_cache()
    devices = run.jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    cfg, weights = run.make_model(cell["config"], args.seeds[0], devices)
    best, failed = None, False
    for rate in sorted(args.rates):
        ok = True
        for seed in args.seeds:
            got = point(cell, cfg, weights, devices, rate, seed, args.seconds)
            got["sustained"] = sustained(got, args.limit_ms)
            ok = ok and got["sustained"]
            print(json.dumps(got), flush=True)
        failed = failed or not ok
        if not failed:
            best = rate
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "limit_ms": args.limit_ms, "knee_req_s": best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
