"""The open-loop driver over a FleetRuntime (one worker per device), on
two virtual CPU devices in a child process: correct when sound, not
correct with an answer altered where the kernel produces it."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

SCRIPT = r"""
import dataclasses, json, pathlib, sys, time
import jax
from bench import harness
from repro.kernels.cnn_eq import cnn_eq as kernels
alter = sys.argv[1] == "1"
if alter:
    real = kernels.cnn_eq_fused_bf16
    def altered(x, *a, **k):
        y = real(x, *a, **k)
        return y.at[:, y.shape[1] // 2].add(0.25)
    kernels.cnn_eq_fused_bf16 = altered
cell = dataclasses.replace(harness.load_cell("lp_serve"), chips=2)
cell.traffic.update(rate_syms_per_s=100e3, grace_s=30.0)
ctx = harness.Ctx(cell=cell, seed=2 ** 31 + 3, seconds=1.0, trace=False,
                  t0=time.perf_counter(), devices=jax.devices()[:2],
                  work_dir=pathlib.Path(sys.argv[2]),
                  sizes={"tenants_per_chip": 2, "base_syms": 8192})
rec = harness.load_driver("open_loop").run(ctx)
line = harness.result_line(cell, rec, trace=False)
print(json.dumps(line))
"""


@pytest.mark.parametrize("alter", [False, True])
def test_fleet_run(alter, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(int(alter)),
                        str(tmp_path)], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 2
    assert line["correct"] is (not alter), line["checks"]
