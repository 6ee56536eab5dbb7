"""Operations and least bytes per symbol, stated here from the layer
shapes by hand and not from the program's `mac_per_symbol`."""
import pytest

from bench import work

PAPER = {"layers": 3, "kernel": 9, "channels": 5, "v_parallel": 8,
         "n_os": 2, "levels": 2}


def test_macs_per_symbol_of_the_paper_topology():
    # 2 samples a symbol. Layer 1 (1 -> 5, K 9) steps 8 samples: a
    # position every 4 symbols, 45 MAC each. Layer 2 (5 -> 5, K 9) keeps
    # that grid: 225 MAC every 4 symbols. Layer 3 (5 -> 8, K 9) steps 2
    # of those: a position every 8 symbols, 360 MAC for its 8 symbols.
    assert work.macs_per_symbol(PAPER) == 45 / 4 + 225 / 4 + 360 / 8
    assert work.flop_per_symbol(PAPER) == 225.0


def test_macs_scale_with_depth_and_oversampling():
    deeper = dict(PAPER, layers=4)                 # one more 5 -> 5 layer
    assert work.macs_per_symbol(deeper) == work.macs_per_symbol(PAPER) \
        + 225 / 4
    # 4 samples a symbol: layers 1 and 2 see twice the positions, layer 3
    # steps 4 of them and keeps one position per 8 symbols
    assert work.macs_per_symbol(dict(PAPER, n_os=4)) == \
        45 / 2 + 225 / 2 + 360 / 8


@pytest.mark.parametrize("backend, in_bytes", [("fused_int8", 1),
                                               ("fused_bf16", 2)])
def test_least_bytes_per_symbol(backend, in_bytes):
    # two samples in at the contract's width, one fp32 symbol out
    assert work.least_bytes_per_symbol(PAPER, backend) == 2 * in_bytes + 4


@pytest.mark.parametrize("backend, w_bytes", [("fused_int8", 1),
                                              ("fused_bf16", 2)])
def test_weight_bytes_per_row(backend, w_bytes):
    n_w = 5 * 1 * 9 + 5 * 5 * 9 + 8 * 5 * 9
    n_b = 5 + 5 + 8
    assert work.weight_bytes_per_row(PAPER, backend) == \
        n_w * w_bytes + 4 * n_b


def test_least_time_names_its_bound():
    # int8 HT step: 468,480 symbols, 64 rows; memory-bound on a v5e
    t, bound = work.least_time_s(PAPER, "fused_int8", 468480, 64,
                                 393e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx((468480 * 6 + 64 * (630 + 72)) / 819e9)
    t, bound = work.least_time_s(PAPER, "fused_int8", 1, 0, 1.0, 1e12)
    assert bound == "compute" and t == 225.0
