"""Hand-computed pins for the work counts and the peak table."""

from types import SimpleNamespace

import pytest

import cells  # noqa: F401  (puts bench/ on the path)
import work

INTERNLM2 = SimpleNamespace(d_model=2048, num_layers=24, num_heads=16,
                            num_kv_heads=8, head_dim=128, d_ff=8192,
                            vocab_size=92544, num_experts=0, moe_top_k=0)
OLMOE8 = SimpleNamespace(d_model=2048, num_layers=8, num_heads=16,
                         num_kv_heads=16, head_dim=128, d_ff=1024,
                         vocab_size=50304, num_experts=64, moe_top_k=8)


def test_internlm2_decode_at_100_positions():
    # per layer: q,o 2*2048*2048 + k,v 2*2048*1024 + ffn 3*2048*8192
    layer = 8_388_608 + 4_194_304 + 50_331_648          # 62_914_560
    head = 2048 * 92544                                  # 189_530_112
    attn = 24 * 4 * 16 * 128 * 100                       # 19_660_800
    flops, nbytes = work.decode_call(INTERNLM2, [100])
    assert flops == 2 * (24 * layer + head) + attn == 3_418_619_904
    kv = 24 * 2 * 8 * 128 * 2                            # 98_304 a position
    weights = 24 * layer * 2 + 49 * 2048 * 2 + head * 2  # bf16, norms too
    assert nbytes == weights + 2048 * 2 + 100 * kv + kv == 3_409_092_608


def test_olmoe_decode_counts_only_the_routed_experts():
    attn = 4 * 2048 * 2048                                # 16_777_216
    experts = 8 * 3 * 2048 * 1024                         # top-8 of 64
    router = 2048 * 64                                    # float32
    head = 2048 * 50304
    flops, nbytes = work.decode_call(OLMOE8, [100])
    assert flops == (2 * (8 * (attn + experts + router) + head)
                     + 8 * 4 * 16 * 128 * 100) == 1_288_437_760
    weights = 8 * ((attn + experts) * 2 + router * 4) + 17 * 2048 * 2 + head * 2
    kv = 8 * 2 * 16 * 128 * 2
    assert nbytes == weights + 4096 + 100 * kv + kv == 1_290_674_176


def test_internlm2_prefill_512():
    layer = 62_914_560
    flops, nbytes = work.prefill_call(INTERNLM2, 512)
    pairs = 512 * 513 // 2
    assert flops == (2 * 512 * 24 * layer + 2 * 2048 * 92544
                     + 24 * 4 * 16 * 128 * pairs) == 1_572_387_422_208
    assert nbytes == 3_399_159_808 + 512 * 2048 * 2 + 512 * 98_304


def test_a_batched_call_reads_the_weights_once():
    one_f, one_b = work.decode_call(INTERNLM2, [100])
    two_f, two_b = work.decode_call(INTERNLM2, [100, 100])
    assert two_f == 2 * one_f
    assert two_b - one_b == 2048 * 2 + 101 * 98_304


def test_bounds_and_peaks():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_bf16"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.needed_seconds(*work.decode_call(INTERNLM2, [100]), peak)
    assert bound == "bytes" and t == pytest.approx(3_409_092_608 / 819e9)
    t, bound = work.needed_seconds(*work.prefill_call(INTERNLM2, 512), peak)
    assert bound == "flops"
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
