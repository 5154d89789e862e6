"""Expert-parallel shard_map MoE vs the single-device oracle.

Runs on a (1, 2)-device mesh in a subprocess (the only other place besides
the dry-run that forces a host device count)."""
import json
import subprocess
import sys

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import moe as moe_lib
from repro.models.moe_ep import moe_mlp_ep, moe_ep_ref, pad_experts

cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                          moe_capacity_factor=8.0)  # no drops
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
pp, E_pad = pad_experts(p, cfg, mesh.shape["model"])
assert E_pad % 2 == 0
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
with jax.set_mesh(mesh):
    out = moe_mlp_ep(pp, x, cfg, mesh)
ref = moe_ep_ref(pp, x, cfg)
err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
print(json.dumps({"err": err}))
assert err < 5e-3, err
"""


def test_moe_ep_matches_oracle():
    out = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True,
        timeout=600, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "HOME": "/root",
                          # forces *host* devices; skip the TPU-backend probe
                          "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    err = json.loads(out.stdout.strip().splitlines()[-1])["err"]
    assert err < 5e-3
