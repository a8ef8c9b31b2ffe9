"""The rounds harness with the experts over four chips (``mesh="on"``, 8
experts on 4 edge shards), on four virtual CPU devices with the look for
the chips skipped: as it stands the run is correct, and with the
exchange between chips left out (every all-to-all hands back what it
was given) it is not.  No cell of ``BENCHMARK.json`` runs on four chips
yet; this keeps the path a four-chip cell would take working, so that
such a cell can be added with data files alone.  Each case runs in a
process of its own, since the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

import bench_paths  # noqa: F401  (first: the import path)
import run

SCRIPT = """
import json, sys
sys.path[:0] = {paths!r}
import jax
if {fault!r} == "exchange":
    jax.lax.all_to_all = lambda x, *a, **k: x
import run, smoke
assert len(jax.devices()) == 4
cell = {{"name": "rounds-mesh4", "config": "bmoe-rounds-fmnist",
         "traffic": "attacked", "chips": 4, "why": "experts over 4 chips"}}
bench = smoke.bench()
for m in bench["end_to_end"]:
    if m["name"] == "round_samples_s":
        m["workloads"].append(cell["name"])
c = smoke.config("bmoe-rounds-fmnist")
c.update(mesh="on", mesh_shards=4, num_experts=8, num_edges=8)
m = smoke.mix("attacked")
m["attack"]["malicious_edges"] = [2]
res = run.execute(bench, cell, 2**31 + 9, 1.0, False, smoke.CPU, smoke.PEAK,
                  c=c, mix=m)
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", ["none", "exchange"])
def test_mesh_rounds_on_four_virtual_devices(fault):
    paths = [str(run.HERE), str(run.ROOT / "src"), str(run.HERE / "tests")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(paths=paths, fault=fault)],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault == "none"), res["checks"]
    assert res["metrics"]["round_samples_s"]["value"] > 0
