"""FLOP and byte counts of both configurations against numbers worked by
hand from the published widths."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import json  # noqa: E402

import pytest  # noqa: E402

from cb.peaks import PEAKS, peaks_for  # noqa: E402
from cb.work import Work  # noqa: E402


def work(name):
    return Work(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen3_8b_d16_by_hand():
    w = work("qwen3-8b.d16")
    # projections 2*4096*(32+16)*128 + 2*32*128*4096, MLP 6*4096*12288
    assert w.layer_flops == 50_331_648 + 33_554_432 + 301_989_888
    assert w.attn_flops_per_key == 4 * 32 * 128
    assert w.head_flops == 2 * 4096 * 151_936
    assert w.kv_bytes_per_key == 64 * 1024          # 16 layers * 4 KiB
    # 16 * 192,946,432 layer parameters + 622,329,856 head + 4096 norm
    assert w.weight_bytes == 2 * (16 * 192_946_432 + 622_333_952)


def test_deepseek_7b_d8_by_hand():
    w = work("deepseek-7b.d8")
    # projections 2*4096*96*128 + 2*32*128*4096, MLP 6*4096*11008
    assert w.layer_flops == 100_663_296 + 33_554_432 + 270_532_608
    assert w.kv_bytes_per_key == 128 * 1024          # 8 layers * 16 KiB
    layer = 4096 * 96 * 128 + 32 * 128 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    assert w.weight_bytes == 2 * (8 * layer + 4096 * 102_400 + 4096)


def test_chunk_and_decode_counts():
    w = work("qwen3-8b.d16")
    # a chunk of 3 tokens after 5: keys 6 + 7 + 8
    assert w.chunk_flops(5, 3) == (3 * 16 * w.layer_flops
                                   + 16 * w.attn_flops_per_key * 21
                                   + w.head_flops)
    flops, nbytes = w.decode_round([10, 20])
    assert flops == w.token_flops(10, True) + w.token_flops(20, True)
    assert nbytes == w.weight_bytes + (30 + 2) * w.kv_bytes_per_key


def test_decode_round_of_32_rows_is_bandwidth_bound_on_v5e():
    w, p = work("qwen3-8b.d16"), peaks_for("TPU v5 lite")
    flops, nbytes = w.decode_round([1200] * 32)
    assert w.least_time(flops, nbytes, p) == pytest.approx(
        nbytes / p["hbm_bytes_per_s"])
    # weights 7.42 GB and 32 x 1200 keys x 64 KiB: about 11.6 ms
    assert 0.011 < w.least_time(flops, nbytes, p) < 0.0125


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
    assert PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
