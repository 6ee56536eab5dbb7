"""The plain reference against the program on the CPU, at a small size:
exact on the int8 datapath, within rounding on bf16; the control one
precision below is far off on both."""
import numpy as np
import pytest

from bench import channels, harness, program, weights
from bench.reference import CONTROL, Reference, max_gap


@pytest.mark.parametrize("config, bound", [("ht_imdd", 0.0),
                                           ("lp_proakis", 1e-4)])
def test_reference_matches_the_engine(config, bound):
    cfg = harness.load_json(harness.BENCH_DIR / "configs" / f"{config}.json")
    topo = cfg["topology"]
    x = channels.waveforms(cfg["channel"], 3, 2, 3000)
    ws = weights.tenant_weights(topo, 2 ** 32 + 1, 2)
    for row, w in zip(x, ws):
        got = np.asarray(program.engine(cfg, w)(row))
        want = Reference(topo, cfg["datapath"], cfg["formats"])(row, w,
                                                                block_pos=64)
        assert got.shape == want.shape == (3000,)
        assert max_gap(got, want) <= bound
        low = Reference(topo, CONTROL[cfg["datapath"]], cfg["formats"])
        assert max_gap(low(row, w), want) > 100 * max(bound, 1e-3)


def test_blocks_do_not_change_the_answer():
    cfg = harness.load_json(harness.BENCH_DIR / "configs" / "ht_imdd.json")
    x = channels.waveforms(cfg["channel"], 1, 1, 2000)[0]
    (w,) = weights.tenant_weights(cfg["topology"], 1, 1)
    ref = Reference(cfg["topology"], "int8", cfg["formats"])
    assert np.array_equal(ref(x, w, block_pos=7), ref(x, w, block_pos=4096))
