"""A cell on more than one chip is data: the configuration's
``build_filter`` shards the particles over a mesh of the cell's chips and takes
the distributed resampler from the traffic mix's ``dra`` object (and,
with its ``domain`` true, one frame tile per chip).  Checked
on four simulated CPU devices at a tiny size, in a child process (the
device count is fixed when JAX starts)."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys
import jax, numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness
cell = harness.load_cell("track-512-n1m")
cfg = {**cell.config}
traffic = {**cell.traffic, "img": 32, "frames": 4, "dra": {"kind": "rpa"}}
obs, _ = cell.model.make_inputs(cfg, traffic, jax.random.key(1), 1)
run, place = cell.model.build_filter(cfg, traffic, 4 * 256, 4)
res = run(jax.random.key(2), place(obs[0]))
text = run.lower(jax.random.key(2), place(obs[0])).as_text()
tiled, _ = cell.model.build_filter(
    cfg, {**traffic, "dra": {"kind": "rna"}, "domain": True}, 4 * 256, 4)
tiled_res = tiled(jax.random.key(2), place(obs[0]))
no_dra = {k: v for k, v in traffic.items() if k != "dra"}
try:
    cell.model.build_filter(cfg, no_dra, 4 * 256, 4)
    refused = False
except ValueError:
    refused = True
print(json.dumps({
    "devices": len(jax.devices()),
    "estimates": list(np.asarray(res.estimates).shape),
    "finite": bool(np.isfinite(np.asarray(res.estimates)).all()
                   and np.isfinite(np.asarray(tiled_res.estimates)).all()),
    "sharded": "all-to-all" in text or "all_to_all" in text
               or "all-gather" in text or "all_gather" in text,
    "refused_without_dra": refused}))
"""


def test_four_chip_offline_filter_is_built_from_data():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"devices": 4, "estimates": [4, 5], "finite": True,
                   "sharded": True, "refused_without_dra": True}
