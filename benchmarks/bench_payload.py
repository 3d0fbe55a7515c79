"""Payload round-trip benchmark: real KV bytes through every physical home.

Drives ``diffusion.payload.RealPayload`` under a ``TieredStore`` + real-mode
``TransferEngine``: bf16 KV pages are fetched from the persistent payload
map into HBM, cascade-demoted to host DRAM and chunked+sha256 spill files as
capacity tightens, and swapped back onto the device on access.  Two hard
gates turn into ERROR rows (failing ``run.py --smoke`` and CI):

  * **byte equality** — every page read back after the full
    HBM -> DRAM -> disk -> HBM tour must equal its persistent original;
  * **bandwidth sanity** — an edge whose aggregate measured bandwidth
    exceeds 10x the roofline of its slower endpoint (``launch.rooflines``)
    is an unblocked-async timing bug, not fast hardware.

Rows report measured bytes/s per tier edge next to the roofline the machine
model predicts.  Writes ``BENCH_payload.json`` (measured-bandwidth history,
uploaded by CI alongside the other ``BENCH_*.json`` artifacts).
"""

from __future__ import annotations

import sys
import tempfile
from typing import List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, "src")
    sys.path.insert(0, "benchmarks")
    from bench_util import append_history
else:
    from .bench_util import append_history

PAGE_MIB = 4.0          # per KV page: large enough for stable timing
PAGES = 6


def main(n: int = None) -> List[Tuple[str, float, str]]:  # noqa: ARG001
    import numpy as np

    from repro.core.index import CentralizedIndex
    from repro.core.store import BandwidthResource
    from repro.diffusion.payload import RealPayload
    from repro.diffusion.tiers import TieredStore, TierSpec
    from repro.diffusion.transfer import TransferEngine

    page_bytes = int(PAGE_MIB * 1024 * 1024)
    rng = np.random.default_rng(0)
    # bf16 via jax (ml_dtypes-backed) so the spill path's dtype-safe byte
    # view is exercised with the dtype the serving plane actually stores.
    import jax.numpy as jnp
    originals = {}
    for i in range(PAGES):
        host = rng.standard_normal(page_bytes // 2).astype(np.float32)
        originals[f"kv:p{i}"] = np.asarray(jnp.asarray(host, jnp.bfloat16))

    with tempfile.TemporaryDirectory(prefix="bench_payload_") as spill:
        idx = CentralizedIndex()
        eng = TransferEngine(idx, BandwidthResource("gpfs", 4e9),
                             payload="real")
        backend = RealPayload("bench", spill_dir=spill)
        # hbm holds 2 pages, dram 2, disk all: admissions cascade-demote so
        # every edge (hbm->dram, dram->disk, disk->hbm, dram->hbm) is hit.
        store = TieredStore(
            "r0",
            [TierSpec("hbm", 2.0), TierSpec("dram", 2.0, 50e9),
             TierSpec("disk", float(PAGES), 2e9)],
            index=idx, nic_bw_bytes_per_s=16e9, payload=backend)
        eng.register("r0", store)
        for obj, host in originals.items():
            eng.put_persistent(obj, host)

        now = 0.0
        for obj in originals:                       # fill: cascades demote
            now += 1.0
            eng.fetch(obj, 1.0, "r0", now)
        for _ in range(2):                          # tour: swap everything in
            for obj in originals:
                now += 1.0
                store.access(obj)
        eng.drain(now=1e9)

        mismatches = []
        for obj, host in originals.items():
            got = backend.get(obj)
            if got is None or not np.array_equal(np.asarray(got), host):
                mismatches.append(obj)
        if mismatches:
            raise RuntimeError(
                f"payload_roundtrip: byte mismatch after tier tour for "
                f"{mismatches} (KV corruption in the payload plane)")
        violations = backend.measured.check_roofline(factor=10.0)
        if violations:
            raise RuntimeError(
                f"payload_roundtrip: measured bandwidth breaks the machine "
                f"model: {violations}")

        rows: List[Tuple[str, float, str]] = []
        history_edges = {}
        roofline = backend.measured.tier_roofline()
        for r in backend.measured.rows():
            edge = f"{r['src']}->{r['dst']}"
            gbps = r["bytes_per_s"] / 1e9
            roof = min(roofline(r["src"]), roofline(r["dst"])) / 1e9
            history_edges[edge] = round(gbps, 3)
            rows.append((
                f"payload_roundtrip/{edge}",
                1e6 * r["seconds"] / max(r["moves"], 1),
                f"measured_gbps={gbps:.3f};roofline_gbps={roof:.1f};"
                f"moves={r['moves']};bytes={int(r['bytes'])}",
            ))
        rows.append((
            "payload_roundtrip/equal",
            0.0,
            f"pages={PAGES};page_mib={PAGE_MIB};byte_equal=True;"
            f"placeholder_fetches={eng.stats.placeholder_fetches}",
        ))
        append_history("BENCH_payload.json", {
            "config": {"pages": PAGES, "page_mib": PAGE_MIB},
            "measured_gbps": history_edges,
            "byte_equal": True,
        })
        return rows


if __name__ == "__main__":
    for row in main():
        print(",".join(map(str, row)))
