"""shard_map expert-parallel MoE vs the dense reference (8 host devices)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models.layers import MoECfg, init_moe, moe
from repro.models.moe_ep import moe_expert_parallel

from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
cfg = MoECfg(num_experts=8, top_k=2, d_ff_expert=32,
             capacity_factor=8.0 / 2 + 0.5)  # lossless
d, T = 16, 64
p, _ = init_moe(jax.random.PRNGKey(0), cfg, d, jnp.float32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(T, d)) * 0.5, jnp.float32)

# dense reference (batch-shaped input)
ref, aux_ref = moe(p, cfg, x[None], act="swiglu")
ref = ref[0]

xs = jax.device_put(x, NamedSharding(mesh, P("model", None)))
ps = {
    "router": jax.device_put(p["router"], NamedSharding(mesh, P(None, None))),
    "w_gate": jax.device_put(p["w_gate"], NamedSharding(mesh, P("model", None, None))),
    "w_up": jax.device_put(p["w_up"], NamedSharding(mesh, P("model", None, None))),
    "w_down": jax.device_put(p["w_down"], NamedSharding(mesh, P("model", None, None))),
}
out, aux = jax.jit(
    lambda ps, xs: moe_expert_parallel(ps, cfg, xs, mesh, act="swiglu"))(ps, xs)
err = float(jnp.max(jnp.abs(out - ref)))
aux_err = abs(float(aux) - float(aux_ref))
print("max_err", err, "aux_err", aux_err)
assert err < 1e-4, err
assert aux_err < 1e-4, (float(aux), float(aux_ref))

# HLO contains explicit all-to-alls, no all-gathers of activations
txt = jax.jit(lambda ps, xs: moe_expert_parallel(ps, cfg, xs, mesh)) \
    .lower(ps, xs).compile().as_text()
assert "all-to-all" in txt
print("MOE_EP_OK")
"""


@pytest.mark.slow
def test_expert_parallel_matches_dense_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "MOE_EP_OK" in out.stdout
