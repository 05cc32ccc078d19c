"""Percentiles, spreads, FLOP and byte counts and the peaks table, each
against a case worked by hand."""

import pytest

from benchmark.lib import flops, peaks, stats


def test_percentile_is_the_ranked_sample():
    xs = [5, 1, 4, 2, 3]
    assert stats.percentile(xs, 0.5) == 3
    assert stats.percentile(xs, 0.95) == 4        # rank int(0.95 * 4) = 3
    assert stats.percentile(list(range(1, 101)), 0.95) == 95
    assert stats.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_spread_is_the_interquartile_share_of_the_median():
    # statistics.quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0


def test_fwd_flops_per_token_gpt2_medium():
    # per layer: q,k,v,o 4 x 1024^2 = 4,194,304; ffn 2 x 1024 x 4096 =
    # 8,388,608; 24 layers x 12,582,912 = 301,989,888 weights in matmuls;
    # x 2 FLOPs = 603,979,776; head 2 x 1024 x 50304 = 103,022,592;
    # attention 2 x 1024 (seq) x 1024 x 24 = 50,331,648
    got = flops.fwd_flops_per_tok(1024, 24, 50304, 4096, 16, 16, False, 1024)
    assert got == 603_979_776 + 103_022_592 + 50_331_648


def test_fwd_flops_per_token_grouped_and_gated():
    # mistral-7b widths, one layer, seq 0: q,o 2 x 4096^2 = 33,554,432;
    # k,v 2 x 4096 x 1024 = 8,388,608; gated ffn 3 x 4096 x 14336 =
    # 176,160,768; sum 218,103,808 (the issue's 218.1 M a layer)
    got = flops.fwd_flops_per_tok(4096, 1, 32000, 14336, 32, 8, True, 0)
    assert got == 2 * 218_103_808 + 2 * 4096 * 32000


def test_mfu():
    # 40,000 tokens/s x 3 x 2.272 GFLOP = 272.6 TFLOP/s over 197 = 1.384
    # on one chip, a quarter of that on four
    f = 757_334_016
    one = flops.mfu(40_000, f, 1, 197e12)
    assert one == pytest.approx(40_000 * 3 * f / 197e12)
    assert flops.mfu(40_000, f, 4, 197e12) == pytest.approx(one / 4)


def test_flash_cost_by_hand():
    # 1 batch, 2 heads, 1 kv head, 4 queries on 4 keys, head 8, causal:
    # live pairs 1+2+3+4 = 10; forward 4 x 8 x 10 x 2 heads = 640 FLOPs;
    # bytes 2 x (2 x (2*4*8) + 2 x (1*4*8)) = 384
    assert flops.flash_attention_cost(1, 2, 1, 4, 4, 8) == (640.0, 384.0)
    # window 2: pairs 1+2+2+2 = 7
    assert flops.flash_attention_cost(1, 2, 1, 4, 4, 8, window=2)[0] == 448.0
    # no mask: 16 pairs
    assert flops.flash_attention_cost(1, 2, 1, 4, 4, 8,
                                      causal=False)[0] == 1024.0
    # queries at the end of 6 keys: 3+4+5+6 = 18
    assert flops.flash_attention_cost(1, 1, 1, 4, 6, 8)[0] == 4 * 8 * 18
    # backward: 2.5 x the forward's FLOPs; 5 query-sized and 4 kv-sized
    f, b = flops.flash_attention_cost(1, 2, 1, 4, 4, 8, backward=True)
    assert (f, b) == (1600.0, 2 * (5 * 64 + 4 * 32))


def test_paged_decode_cost_by_hand():
    # two sequences at contexts 3 and 5, 4 query heads on 2 kv heads of 8:
    # FLOPs 4 x 8 x (3+5) x 4 = 1024; bytes 2 x (2 x 8 x 2 x 8 [k,v rows]
    # + 2 x 2 x 4 x 8 [q and out, twice]) = 2 x (256 + 128) = 768
    assert flops.paged_decode_cost([3, 5], 4, 2, 8) == (1024.0, 768.0)
    assert flops.paged_decode_cost([3, 5], 4, 2, 8, window=4)[0] == \
        4 * 8 * (3 + 4) * 4


def test_peaks_table_has_the_v5e_and_no_default():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["flops_per_s_bf16"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imagined")
