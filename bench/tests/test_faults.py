"""A whole run of each cell's driver on the CPU at a small size, past the
runner's look for a chip: sound, it is correct; with the control (the
reference one precision below, in the program's place) or with an answer
altered where the kernel produces it, it is not."""
import time

import jax
import pytest

from bench import harness
from repro.kernels.cnn_eq import cnn_eq as kernels

SMALL = {
    "ht_bulk": ({"n_instances": 4, "l_inst": 2048}, None),
    "lp_serve": ({"tenants_per_chip": 4, "base_syms": 8192}, 100e3),
}
KERNEL = {"fused_int8": "cnn_eq_fused_int8", "fused_bf16": "cnn_eq_fused_bf16"}


def _run(name, tmp_path, control=False):
    sizes, rate = SMALL[name]
    cell = harness.load_cell(name)
    if rate is not None:
        cell.traffic.update(rate_syms_per_s=rate, grace_s=30.0)
    ctx = harness.Ctx(cell=cell, seed=2 ** 31 + 17, seconds=1.0,
                      trace=False, t0=time.perf_counter(),
                      devices=jax.devices()[:1], work_dir=tmp_path,
                      control=control, sizes=sizes)
    rec = harness.load_driver(cell.traffic["driver"]).run(ctx)
    return harness.result_line(cell, rec, trace=False)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name, tmp_path):
    line = _run(name, tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_not_correct(name, tmp_path):
    line = _run(name, tmp_path, control=True)
    assert not line["correct"]
    gap = line["checks"]["max_abs_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_altered_answer_is_not_correct(name, tmp_path, monkeypatch):
    cell = harness.load_cell(name)
    fn_name = KERNEL[cell.config["backend"]]
    real = getattr(kernels, fn_name)

    def altered(x, *args, **kw):
        y = real(x, *args, **kw)              # one kept symbol of each row
        return y.at[:, y.shape[1] // 2].add(0.25)

    monkeypatch.setattr(kernels, fn_name, altered)
    line = _run(name, tmp_path)
    assert not line["correct"]
    assert line["checks"]["max_abs_gap"]["value"] >= 0.2


def test_window_arms_no_stack_dump_watchdog(tmp_path, monkeypatch):
    """A watchdog that dumps the stacks of running threads reads their
    frames without the interpreter lock, and crashes the process (SIGSEGV)
    when the runtime's threads change under it: no run arms one."""
    import faulthandler

    def refuse(*_a, **_k):
        raise AssertionError("a stack-dump watchdog was armed")

    monkeypatch.setattr(faulthandler, "dump_traceback_later", refuse)
    line = _run("lp_serve", tmp_path)
    assert line["correct"], line["checks"]
