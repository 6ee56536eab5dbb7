"""The LP read-channel fleet on four devices, as the `lp_fleet4` cell
deploys it (bench/configs/lp_proakis_fleet4.json), at a CPU size.

A child process with four virtual CPU devices builds the fleet through
the benchmark's own path (`bench.program.runtime`): a 4-worker
`FleetRuntime` with 2 tenants a worker, the `lp_proakis` topology and
12-bit formats on `fused_bf16`, seeded random weights and seeded Proakis-B
waveforms. Every tenant streams jittered chunks (1,024 symbols ±50 %),
interleaved across tenants. Then:

  * every tenant's emitted symbols equal the plain float64 reference
    (`bench/reference.py`) within the configuration's `max_abs_gap`, and
    no due symbol is missing;
  * the same chunks through the one-device `AsyncServeRuntime` give the
    same symbols bitwise (contract #10: output independent of placement);
  * placement puts 2 tenants on each worker, and each worker's launches
    landed on its own device only.
"""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

SCRIPT = r"""
import json
import jax
import numpy as np
from bench import channels, harness, program, weights
from bench.reference import Reference, max_gap, receptive_halo, \
    total_stride

cfg = harness.load_json(harness.ROOT / "bench/configs/lp_proakis_fleet4.json")
cfg["deployment"]["tenants_per_chip"] = 2
topo, seed = cfg["topology"], 2 ** 31 + 17
devs = jax.devices()
n, n_os = 2 * len(devs), topo["n_os"]
waves = channels.waveforms(cfg["channel"], seed, n, 3072)
ws = weights.tenant_weights(topo, seed, n)
rng = np.random.default_rng(seed)
parts = []                        # each tenant's chunks, stream order
for i in range(n):
    cuts = np.cumsum(np.rint(1024 * n_os * rng.uniform(
        0.5, 1.5, 8)).astype(int))
    parts.append(np.split(waves[i], cuts[cuts < waves.shape[1]]))
# tenants interleaved at random; each tenant's chunks stay in order
order = rng.permutation(np.repeat(np.arange(n), [len(p) for p in parts]))
tids = [f"t{i}" for i in range(n)]


def serve(devices):
    rt = program.runtime(cfg, devices, None)
    try:
        for tid, w in zip(tids, ws):
            rt.open(program.tenant_spec(cfg, tid, w))
        sent = [0] * n
        for i in order:
            rt.submit(tids[i], parts[i][sent[i]])
            sent[i] += 1
        rt.drain()
        out = [rt.output(tid) for tid in tids]
        return out, rt.stats()
    finally:
        rt.shutdown()


fleet, st = serve(devs)
one, _ = serve(devs[:1])
ref = Reference(topo, cfg["datapath"], cfg["formats"])
ts, halo, vp = total_stride(topo), receptive_halo(topo), topo["v_parallel"]
m = waves.shape[1]
due = min((m - 1 - halo) // ts + 1, m // ts) * vp
print(json.dumps({
    "devices": [str(d) for d in devs],
    "gaps": [max_gap(got, ref(waves[i], ws[i])[:got.shape[0]])
             for i, got in enumerate(fleet)],
    "missing": [max(0, due - got.shape[0]) for got in fleet],
    "bitwise": [a.shape == b.shape and a.tobytes() == b.tobytes()
                for a, b in zip(fleet, one)],
    "workers": [{"device": w["device"], "tenants": w["tenants"],
                 "launch_devices": w["launch_devices"]}
                for w in st["workers"]],
    "limit": cfg["limits"]["max_abs_gap"],
}))
"""


def test_lp_fleet_on_four_devices_matches_reference_and_one_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert len(out["devices"]) == 4
    assert max(out["gaps"]) <= out["limit"], out["gaps"]
    assert out["missing"] == [0] * 8
    assert all(out["bitwise"]), out["bitwise"]
    for w, dev in zip(out["workers"], out["devices"]):
        assert w["device"] == dev
        assert len(w["tenants"]) == 2, w
        assert set(w["launch_devices"]) == {dev}, w
