"""Operations and bytes of the kernels and model steps, against values
worked out by hand, and the peaks table.  The sizes come through the dense
architecture file, as a cell's do."""

import json

import pytest

from bench import counting, loader

DENSE = loader.load_arch("dense")
D = DENSE.dims({"hidden_size": 8, "intermediate_size": 16,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 2, "num_hidden_layers": 2, "vocab_size": 10})
PEAKS = counting.Peaks(flops_per_s=1e12, bytes_per_s=1e9, hbm_bytes=1e9,
                       source="test")


def test_decode_call_by_hand():
    # slots at positions 9 and 19 attend 10 and 20 rows: 30 rows in all
    c = counting.ragged_decode_call(D, [10, 20])
    # QK and PV: 2 * 2 * Hq * hd FLOPs per row = 32 per row
    assert c.flops == 32 * 30
    # K and V per KV head per row: 2 * 2 heads * 2 hd * 2 bytes = 16 B/row;
    # per slot q (4 * 2 * 2 B) and the f32 output (4 * 2 * 4 B) = 48 B
    assert c.bytes == 16 * 30 + 2 * 48


def test_prefill_call_by_hand():
    # chunk of 3 live rows starting at 5: rows attend 6, 7, 8 keys
    c = counting.ragged_prefill_call(D, start=5, qlen=3)
    assert c.flops == 32 * (6 + 7 + 8)
    # K/V up to the horizon 8 rows (16 B each), q+out per live row 48 B
    assert c.bytes == 16 * 8 + 3 * 48


def test_model_flops_by_hand():
    # per token per layer: qkv 8 * (4 + 4) * 2, out 8 * 8, mlp 3 * 8 * 16
    per_layer = 8 * 8 * 2 + 8 * 8 + 3 * 8 * 16
    assert D.matmul_flops_per_token == 2 * 2 * per_layer
    assert counting.lm_head_flops(D) == 2 * 8 * 10
    dec = counting.decode_model_flops(D, [10, 20])
    assert dec == 2 * (2 * 2 * per_layer + 160) + 32 * 30 * 2
    pre = counting.prefill_model_flops(D, 5, 3, last_chunk=True)
    assert pre == 3 * 2 * 2 * per_layer + 32 * 21 * 2 + 160
    assert counting.prefill_model_flops(D, 5, 3, last_chunk=False) == \
        pre - 160


def test_least_time_is_the_larger_bound():
    assert counting.Cost(flops=2e12, bytes=1e9).least_s(PEAKS) == 2.0
    assert counting.Cost(flops=1e9, bytes=3e9).least_s(PEAKS) == 3.0


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counting.peaks_for("TPU v99")


def test_v5e_peaks_and_source():
    p = counting.peaks_for("TPU v5 lite")
    assert p.flops_per_s == 197e12 and p.bytes_per_s == 819e9
    assert "TPU v5e" in p.source
    table = json.loads(counting.PEAKS.read_text())
    assert all("source" in v for v in table.values())


@pytest.mark.parametrize("rows", [[1], [1, 128, 129], [4096] * 64,
                                  [7, 300, 2049, 4000]])
def test_useful_work_never_exceeds_what_the_kernel_does(rows):
    """The roofline share cannot pass 100% by construction: the counted
    bytes and FLOPs are at most what the kernel really moves and computes,
    which reads whole 128-row blocks for every slot, idle ones included."""
    d = DENSE.dims({
        "hidden_size": 896, "intermediate_size": 4864,
        "num_attention_heads": 14, "num_key_value_heads": 2,
        "num_hidden_layers": 24, "vocab_size": 151936})
    slots = 64
    blocks = [-(-r // 128) * 128 for r in rows] + [128] * (slots - len(rows))
    done = counting.ragged_decode_call(d, blocks)
    useful = counting.ragged_decode_call(d, rows)
    assert useful.flops <= done.flops and useful.bytes <= done.bytes
    assert useful.least_s(counting.peaks_for("TPU v5 lite")) <= \
        done.least_s(counting.peaks_for("TPU v5 lite"))
