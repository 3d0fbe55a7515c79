"""Chip smoke test: serve internlm2-1.8b at full width on a TPU.

    python chip_smoke.py             # one chip: two replicas behind the
                                     # cache-affinity router, host-DRAM tier,
                                     # real KV swap-ins, every request checked
                                     # against a cache-free reference
    python chip_smoke.py --chips 4   # four chips: one replica per device vs
                                     # all four on device 0, same stream

Weights are random, drawn from ``--seed``.  There is no CPU fallback: the
script exits nonzero unless JAX finds a TPU.  Everything runs in this one
process.  The last line of stdout is the JSON verdict.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the one-replica-per-device placement "
                         "phase and its all-on-device-0 comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform is "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")

    from repro.configs import get_arch
    from repro.launch.smoke import placement_smoke, serve_smoke
    print(f"compile cache: {cache_dir}", flush=True)
    cfg = get_arch("internlm2-1.8b")            # full width, never reduced

    def log(line: str) -> None:
        print(line, flush=True)

    if args.chips == 4:
        placement_smoke(cfg, seed=args.seed, log=log)
    else:
        serve_smoke(cfg, seed=args.seed, log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
