"""Payload plane: measured KV-byte movement under the tier bookkeeping.

Tier-1 (fake backend, no accelerator): the MeasuredBandwidth accumulator,
placeholder tolerance, store-hook movement, and — the load-bearing parity
contract — ``payload="modeled"`` and ``payload="real"`` transfer engines
making bit-identical promote/demote/fetch decisions over the same stream.

Slow (real backend): byte-equality of KV pages round-tripped through every
physical home (HBM device arrays -> host numpy -> chunked+sha256 spill
files -> HBM), chunk corruption detection, and the real serving loop
measuring actual dram->hbm swap-in bandwidth without perturbing routing.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.index import CentralizedIndex
from repro.core.store import BandwidthResource
from repro.diffusion.payload import FakePayload, MeasuredBandwidth, NullPayload
from repro.diffusion.tiers import TieredStore, TierSpec, roofline_tier_bw
from repro.diffusion.transfer import TransferEngine


def kv_tree(seed: int, n: int = 256) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "k": rng.standard_normal((2, n)).astype(np.float32),
        "v": [rng.standard_normal(n).astype(np.float32),
              rng.integers(0, 100, size=n).astype(np.int32)],
    }


def tree_equal(a, b) -> bool:
    return (np.array_equal(a["k"], b["k"])
            and np.array_equal(a["v"][0], b["v"][0])
            and np.array_equal(a["v"][1], b["v"][1]))


# --------------------------------------------------------- accumulator

class TestMeasuredBandwidth:
    def test_accumulates_per_edge(self):
        m = MeasuredBandwidth()
        m.record("dram", "hbm", 100.0, 2.0)
        m.record("dram", "hbm", 300.0, 2.0)
        m.record("hbm", "dram", 50.0, 1.0)
        assert m.bandwidth("dram", "hbm") == 100.0
        assert m.bandwidth("hbm", "dram") == 50.0
        assert m.bandwidth("disk", "hbm") == 0.0
        assert m.total_bytes == 450.0
        rows = m.rows()
        assert [(r["src"], r["dst"]) for r in rows] == \
            [("dram", "hbm"), ("hbm", "dram")]
        assert rows[0]["moves"] == 2

    def test_merge(self):
        a, b = MeasuredBandwidth(), MeasuredBandwidth()
        a.record("dram", "hbm", 10.0, 1.0)
        b.record("dram", "hbm", 30.0, 1.0)
        b.record("hbm", "disk", 8.0, 2.0)
        a.merge(b)
        assert a.bandwidth("dram", "hbm") == 20.0
        assert a.bandwidth("hbm", "disk") == 4.0

    def test_roofline_check_flags_impossibly_fast(self):
        m = MeasuredBandwidth()
        roof = min(roofline_tier_bw("dram"), roofline_tier_bw("hbm"))
        m.record("dram", "hbm", roof * 100.0, 1.0)   # 100x the roofline
        bad = m.check_roofline(factor=10.0)
        assert len(bad) == 1 and "dram->hbm" in bad[0]
        # slower than roofline is normal, never flagged
        m2 = MeasuredBandwidth()
        m2.record("dram", "hbm", roof * 0.01, 1.0)
        assert m2.check_roofline() == []

    def test_roofline_check_holds_a_device_run_to_its_kind(self):
        """A run on a real device is held to that device's host link, not
        the DES's modeled dram calibration (ICI)."""
        from repro.launch.rooflines import device_peaks
        link = device_peaks("TPU v5 lite").host_link_bw
        m = MeasuredBandwidth()
        m.record("dram", "hbm", link * 12.0, 1.0)   # 12x the host link
        assert m.check_roofline(factor=10.0) == []  # under 10x modeled ICI
        m.device_kind = "TPU v5 lite"
        bad = m.check_roofline(factor=10.0)
        assert len(bad) == 1 and "dram->hbm" in bad[0]

    def test_roofline_check_unknown_device_kind_is_an_error(self):
        m = MeasuredBandwidth()
        m.device_kind = "TPU v99"
        m.record("dram", "hbm", 1.0, 1.0)
        with pytest.raises(ValueError, match="TPU v99"):
            m.check_roofline()

    def test_peak_table_is_keyed_by_device_kind_with_a_source(self):
        from repro.launch.rooflines import PEAKS, REFERENCE, device_peaks
        for kind, peaks in PEAKS.items():
            assert peaks.kind == kind and peaks.source
            assert peaks.host_link_bw < peaks.hbm_bw
        assert device_peaks("TPU v5 lite") is REFERENCE
        assert REFERENCE.flops_bf16 == 197e12 and REFERENCE.hbm_bw == 819e9

    def test_merge_keeps_the_device_kind(self):
        a, b = MeasuredBandwidth(), MeasuredBandwidth()
        b.device_kind = "TPU v5 lite"
        a.merge(b)
        assert a.device_kind == "TPU v5 lite"

    def test_roofline_check_skips_modeled_sources(self):
        # engine edges ("persistent"/"peer" -> tier) ride modeled wires; an
        # in-process memcpy legitimately beats them and must not be flagged.
        m = MeasuredBandwidth()
        m.record("persistent", "hbm", 1e15, 1.0)
        m.record("peer", "dram", 1e15, 1.0)
        assert m.check_roofline() == []


# --------------------------------------------------------- fake backend

class TestFakePayload:
    def test_roundtrip_and_modeled_timing(self):
        p = FakePayload()
        tree = kv_tree(0)
        p.put("kv:a", tree, "hbm")
        assert p.has("kv:a") and p.tier_of("kv:a") == "hbm"
        assert p.nbytes("kv:a") > 0
        p.moved("kv:a", "dram")
        p.moved("kv:a", "disk")
        p.moved("kv:a", "hbm")
        assert tree_equal(p.get("kv:a"), tree)
        # modeled seconds: size over the slower endpoint's roofline, so the
        # measured rows are bit-reproducible without an accelerator
        nb = p.nbytes("kv:a")
        exp = nb / min(roofline_tier_bw("hbm"), roofline_tier_bw("dram"))
        assert p.measured._acc[("hbm", "dram")][1] == pytest.approx(exp)
        assert p.measured.check_roofline() == []

    def test_placeholders_counted_not_fatal(self):
        p = FakePayload()
        p.moved("kv:ghost", "hbm")
        p.dropped("kv:ghost")
        assert p.placeholder_moves == 1
        assert p.get("kv:ghost") is None
        n = NullPayload()
        n.put("kv:a", kv_tree(1), "hbm")     # stores nothing by design
        n.moved("kv:a", "dram")
        assert n.placeholder_moves == 1 and not n.has("kv:a")

    def test_same_tier_move_is_noop(self):
        p = FakePayload()
        p.put("kv:a", kv_tree(2), "hbm")
        p.moved("kv:a", "hbm")
        assert p.measured.rows() == []

    def test_store_hooks_move_and_drop(self):
        idx = CentralizedIndex()
        p = FakePayload()
        st = TieredStore("r0", [TierSpec("hbm", 2.0), TierSpec("dram", 4.0)],
                         index=idx, payload=p)
        st.admit("kv:a", 1.0)                # placeholder: no bytes yet
        assert p.placeholder_moves == 1
        p.put("kv:a", kv_tree(3), "hbm")
        st.demote("kv:a", 1)                 # hbm -> dram moves real bytes
        assert p.tier_of("kv:a") == "dram"
        st.access("kv:a")                    # promote back
        assert p.tier_of("kv:a") == "hbm"
        st.drop("kv:a")
        assert not p.has("kv:a")
        assert [(r["src"], r["dst"]) for r in p.measured.rows()] == \
            [("dram", "hbm"), ("hbm", "dram")]

    def test_eviction_cascade_demotes_payload(self):
        idx = CentralizedIndex()
        p = FakePayload()
        st = TieredStore("r0", [TierSpec("hbm", 1.0), TierSpec("dram", 1.0)],
                         index=idx, payload=p)
        st.admit("kv:a", 1.0)
        p.put("kv:a", kv_tree(4), "hbm")
        st.admit("kv:b", 1.0)                # victim kv:a demotes to dram
        assert st.tier_of("kv:a") == "dram" and p.tier_of("kv:a") == "dram"
        st.admit("kv:c", 1.0)                # kv:a falls off the node
        assert not st.contains("kv:a") and not p.has("kv:a")


# --------------------------------------------- modeled == real decisions

def _drive_engine(payload_mode: str):
    """One deterministic fetch/access/demote/cancel stream; returns the
    decision-observable trace (sources, contents, stats) plus the engine."""
    idx = CentralizedIndex()
    link = BandwidthResource("gpfs", 4e9)
    eng = TransferEngine(idx, link, max_inflight=2, payload=payload_mode)
    stores = {}
    for i in range(3):
        st = TieredStore(f"r{i}",
                         [TierSpec("hbm", 2.0), TierSpec("dram", 4.0, 50e9)],
                         index=idx, nic_bw_bytes_per_s=16e9,
                         payload=FakePayload() if payload_mode == "real"
                         else None)
        stores[f"r{i}"] = st
        eng.register(f"r{i}", st)
    for o in range(4):
        eng.put_persistent(f"kv:{o}", kv_tree(o))
    trace = []
    now = 0.0
    for step, (o, d) in enumerate(
            [(0, 0), (1, 0), (0, 1), (2, 2), (0, 2), (3, 1), (1, 2), (2, 0)]):
        now += 0.5
        tr = eng.fetch(f"kv:{o}", 1.0, f"r{d}", now)
        trace.append(("fetch", f"kv:{o}", f"r{d}", tr.source if tr else None))
        if step % 3 == 2:
            stores[f"r{d}"].demote(f"kv:{o}", 1)
        if step % 4 == 3:
            stores[f"r{d}"].access(f"kv:{o}")
        trace.append(("contents",
                      {n: s.contents() for n, s in sorted(stores.items())}))
    eng.drain(now=1e9)
    key_stats = (eng.stats.started, eng.stats.completed, eng.stats.shared,
                 eng.stats.peer_fetches, eng.stats.persistent_fetches)
    return trace, key_stats, eng, stores


def test_modeled_and_real_payload_make_identical_decisions():
    """The payload plane must be measurement-only: every source choice,
    admission, tier layout, and engine counter is bit-identical whether the
    engine moves real bytes (fake backend) or none at all."""
    m_trace, m_stats, m_eng, _ = _drive_engine("modeled")
    r_trace, r_stats, r_eng, r_stores = _drive_engine("real")
    assert m_trace == r_trace
    assert m_stats == r_stats
    # and the real run actually moved bytes (it wasn't placeholder-only)
    assert r_eng.stats.payload_moves > 0
    assert r_eng.stats.payload_bytes_moved > 0
    assert m_eng.stats.payload_moves == 0
    # fetched copies are byte-equal to the persistent source everywhere
    for name, st in r_stores.items():
        backend = st.payload
        for obj in st.contents():
            if backend.has(obj):
                o = int(obj.split(":")[1])
                assert tree_equal(backend.get(obj), kv_tree(o))


def test_payload_bytes_withdrawn_on_cancel():
    """A preempted flight's early-admitted placeholder withdraws its real
    bytes too (store.drop -> backend.dropped through the hook)."""
    idx = CentralizedIndex()
    eng = TransferEngine(idx, BandwidthResource("gpfs", 4e9),
                         max_inflight=1, payload="real")
    st = TieredStore("r0", [TierSpec("hbm", 8.0)], index=idx,
                     nic_bw_bytes_per_s=16e9, payload=FakePayload())
    eng.register("r0", st)
    eng.put_persistent("kv:spec", kv_tree(9))
    eng.put_persistent("kv:hot", kv_tree(10))
    eng.fetch("kv:spec", 1.0, "r0", 0.0, kind="prefetch")
    assert st.payload.has("kv:spec")
    eng.fetch("kv:hot", 1.0, "r0", 0.0)      # demand preempts the prefetch
    assert eng.stats.preempted == 1
    assert not st.payload.has("kv:spec")     # bytes withdrawn with the entry
    assert st.payload.has("kv:hot")


# ------------------------------------------------------------ real homes

@pytest.mark.slow
class TestRealPayloadRoundTrip:
    def test_kv_page_roundtrip_all_homes(self, tmp_path):
        """HBM -> DRAM -> disk -> HBM, byte-equal at the end (bf16 KV page,
        chunked spill with per-chunk sha256 verified on the way back)."""
        import jax.numpy as jnp
        from repro.diffusion.payload import RealPayload

        page = {
            "k": jnp.asarray(
                np.random.default_rng(0).standard_normal((4, 64, 8)),
                jnp.bfloat16),
            "v": jnp.asarray(
                np.random.default_rng(1).standard_normal((4, 64, 8)),
                jnp.bfloat16),
        }
        host0 = {k: np.asarray(v) for k, v in page.items()}
        p = RealPayload("t", spill_dir=str(tmp_path), chunk_bytes=1024)
        p.put("kv:page", page, "hbm")
        for tier in ("dram", "disk", "hbm"):
            p.moved("kv:page", tier)
        got = p.get("kv:page")
        assert np.array_equal(np.asarray(got["k"]), host0["k"])
        assert np.array_equal(np.asarray(got["v"]), host0["v"])
        edges = [(r["src"], r["dst"]) for r in p.measured.rows()]
        assert set(edges) == {("hbm", "dram"), ("dram", "disk"),
                              ("disk", "hbm")}
        assert p.measured.check_roofline(factor=10.0) == []
        # spill chunks were freed when the page left the disk home
        assert list(tmp_path.glob("*.kv")) == []

    def test_spill_corruption_detected(self, tmp_path):
        from repro.diffusion.payload import RealPayload
        p = RealPayload("t", spill_dir=str(tmp_path), chunk_bytes=512)
        arr = np.arange(1024, dtype=np.float32)
        p.put("kv:x", arr, "dram")
        p.moved("kv:x", "disk")
        chunk = sorted(tmp_path.glob("*.kv"))[0]
        raw = bytearray(chunk.read_bytes())
        raw[0] ^= 0xFF
        chunk.write_bytes(bytes(raw))
        with pytest.raises(IOError, match="corrupt"):
            p.get("kv:x")

    def test_serving_swap_in_measured_without_perturbing_decisions(self):
        """The real serving loop: HBM evictions demote actual KV tensors,
        swap-ins device_put them back (measured), and the routing decisions
        match the modeled run bit-for-bit."""
        from repro.configs import get_arch
        from repro.runtime.serve_loop import DiffusionServer

        cfg = get_arch("internlm2-1.8b").reduced()
        rng = np.random.default_rng(0)
        prompts = {f"s{i}": rng.integers(0, cfg.vocab_size, size=(12,))
                   for i in range(3)}

        def run(payload):
            srv = DiffusionServer(cfg, policy="good-cache-compute",
                                  max_replicas=1, min_replicas=1,
                                  cache_cap=48, max_sessions=2,
                                  host_cache_sessions=4, seed=1,
                                  payload=payload)
            for _ in range(2):
                for sid, p in prompts.items():
                    srv.submit(sid, p, max_new_tokens=2)
                srv.step()
            return srv

        real, modeled = run("real"), run("modeled")
        for srv in (real, modeled):
            assert srv.stats.swap_ins >= 1
        assert real.stats.swap_ins == modeled.stats.swap_ins
        assert real.stats.prefix_hits == modeled.stats.prefix_hits
        assert real.stats.prefills == modeled.stats.prefills
        # the real run measured actual dram->hbm byte movement
        assert real.swap_in_bandwidth() > 0.0
        assert real.measured.total_bytes > 0
        assert real.measured.check_roofline(factor=10.0) == []
        assert modeled.measured.total_bytes == 0


# ------------------------------------------- host shadow staged at put

class _CountingNumpy:
    """``numpy`` for the payload module, counting conversions of device
    arrays to host arrays."""

    def __init__(self):
        self.conversions = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def _counted(self, fn, a, *args, **kwargs):
        import jax
        if isinstance(a, jax.Array):
            self.conversions += 1
        return fn(a, *args, **kwargs)

    def asarray(self, a, *args, **kwargs):
        return self._counted(np.asarray, a, *args, **kwargs)

    def array(self, a, *args, **kwargs):
        return self._counted(np.array, a, *args, **kwargs)

    def ascontiguousarray(self, a, *args, **kwargs):
        return self._counted(np.ascontiguousarray, a, *args, **kwargs)


def device_kv(seed: int) -> dict:
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return {
        "k": jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.bfloat16),
        "v": [jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.bfloat16),
              jnp.asarray(rng.integers(0, 100, size=8), jnp.int32)],
    }


def host_of(tree: dict) -> dict:
    return {"k": np.array(tree["k"]),
            "v": [np.array(tree["v"][0]), np.array(tree["v"][1])]}


class TestStagedDemotion:
    def test_put_of_device_arrays_registers_them_without_a_host_copy(
            self, monkeypatch):
        import jax
        from jax._src.array import ArrayImpl
        from repro.diffusion import payload
        counting = _CountingNumpy()
        waits = []
        dunder = []
        puts = []
        array_fn = ArrayImpl.__array__
        monkeypatch.setattr(payload, "np", counting)
        monkeypatch.setattr(
            ArrayImpl, "__array__",
            lambda self, *a, **k: dunder.append(1) or array_fn(self, *a, **k))
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waits.append(x) or x)
        monkeypatch.setattr(jax, "device_put",
                            lambda *a, **k: puts.append(a))
        tree = device_kv(0)
        p = payload.RealPayload("t")
        p.put("kv:a", tree, "hbm")
        assert counting.conversions == 0 and not dunder
        assert not waits and not puts
        held = p.value("kv:a")
        assert held["k"] is tree["k"] and held["v"][1] is tree["v"][1]
        assert p.nbytes("kv:a") == sum(
            l.nbytes for l in (tree["k"], *tree["v"]))

    def test_demotion_adopts_the_shadow_as_a_staged_move(self):
        tree = device_kv(1)
        host0 = host_of(tree)
        from repro.diffusion.payload import RealPayload
        p = RealPayload("t")
        p.put("kv:a", tree, "hbm")
        p.moved("kv:a", "dram")
        assert (p.demotions, p.staged_demotions) == (1, 1)
        home = p.value("kv:a")          # the DRAM home's own arrays
        for got, leaf in ((home["k"], tree["k"]),
                          (home["v"][0], tree["v"][0])):
            assert not np.shares_memory(got, np.asarray(leaf))
        for leaf in (tree["k"], *tree["v"]):
            leaf.delete()               # the DRAM home owns its bytes
        assert tree_equal(p.get("kv:a"), host0)
        (row,) = p.measured.rows()
        assert (row["src"], row["dst"]) == ("hbm", "dram")
        assert row["staged_bytes"] == p.nbytes("kv:a") > 0
        assert row["staged_moves"] == 1
        assert row["bytes"] == row["seconds"] == row["moves"] == 0
        assert p.measured.total_bytes == 0.0
        assert p.measured.check_roofline() == []

    def test_demotion_after_a_promotion_is_timed(self):
        """Promoted bytes were not staged: their next demotion copies and
        is timed as before."""
        from repro.diffusion.payload import RealPayload
        tree = device_kv(2)
        host0 = host_of(tree)
        p = RealPayload("t")
        p.put("kv:a", tree, "hbm")
        for tier in ("dram", "hbm", "dram"):
            p.moved("kv:a", tier)
        assert (p.demotions, p.staged_demotions) == (2, 1)
        edge = {(r["src"], r["dst"]): r for r in p.measured.rows()}
        assert edge[("hbm", "dram")]["moves"] == 1
        assert edge[("hbm", "dram")]["staged_moves"] == 1
        assert edge[("dram", "hbm")]["moves"] == 1
        assert tree_equal(p.get("kv:a"), host0)

    def test_host_array_put_demotes_as_a_timed_move(self):
        from repro.diffusion.payload import RealPayload
        tree = kv_tree(3)
        p = RealPayload("t")
        p.put("kv:a", tree, "hbm")
        p.moved("kv:a", "dram")
        assert (p.demotions, p.staged_demotions) == (1, 0)
        (row,) = p.measured.rows()
        assert row["moves"] == 1 and row["bytes"] == p.nbytes("kv:a") > 0
        assert row["seconds"] > 0
        assert row["staged_moves"] == 0 and row["staged_bytes"] == 0.0
        assert tree_equal(p.get("kv:a"), tree)

    def test_peer_get_of_a_staged_object_is_byte_equal(self):
        from repro.diffusion.payload import RealPayload
        tree = device_kv(4)
        host0 = host_of(tree)
        p = RealPayload("t")
        p.put("kv:a", tree, "hbm")
        got = p.get("kv:a")
        assert tree_equal(got, host0)
        assert all(isinstance(l, np.ndarray)
                   for l in (got["k"], *got["v"]))
        assert p.tier_of("kv:a") == "hbm" and p.measured.rows() == []
        q = RealPayload("peer")             # the fetching replica's copy
        q.put("kv:a", got, "dram")
        assert tree_equal(q.get("kv:a"), host0)

    def test_staged_moves_sit_beside_timed_ones(self):
        m = MeasuredBandwidth()
        m.record_staged("hbm", "dram", 64.0)
        assert m.bandwidth("hbm", "dram") == 0.0 and m.total_bytes == 0.0
        assert m.check_roofline() == []
        m.record("hbm", "dram", 10.0, 1.0)
        other = MeasuredBandwidth()
        other.record_staged("hbm", "dram", 36.0)
        m.merge(other)
        (row,) = m.rows()
        assert (row["bytes"], row["seconds"], row["moves"]) == (10.0, 1.0, 1)
        assert (row["staged_bytes"], row["staged_moves"]) == (100.0, 2)
        assert m.bandwidth("hbm", "dram") == 10.0

    def test_server_demotions_adopt_staged_copies(self):
        """The reduced serving loop: HBM evictions of sessions put at the
        end of their turn adopt the staged copy, and every token matches
        the modeled run, whose KV never leaves the device."""
        from repro.configs import get_arch
        from repro.runtime.serve_loop import DiffusionServer

        cfg = get_arch("internlm2-1.8b").reduced()
        rng = np.random.default_rng(0)
        prompts = {f"s{i}": rng.integers(0, cfg.vocab_size, size=(12,))
                   for i in range(3)}

        def run(payload):
            srv = DiffusionServer(cfg, policy="good-cache-compute",
                                  max_replicas=1, min_replicas=1,
                                  cache_cap=48, max_sessions=2,
                                  host_cache_sessions=4, seed=1,
                                  payload=payload)
            reqs = []
            for _ in range(2):
                reqs += [srv.submit(sid, p, max_new_tokens=2)
                         for sid, p in prompts.items()]
                srv.step()
            return srv, [[int(t[0]) for t in r.generated] for r in reqs]

        (real, real_tokens), (modeled, modeled_tokens) = (
            run("real"), run("modeled"))
        assert real.stats.swap_ins >= 1
        assert real.staged_demotion_share() > 0.0
        assert modeled.staged_demotion_share() == 0.0
        assert real_tokens == modeled_tokens
        assert real.measured.check_roofline() == []
