"""Empirical validation of the roofline methodology's core assumptions:

  1. ``compiled.cost_analysis()`` on the forced-host backend reports
     PER-DEVICE, post-partitioning flops (2*M*N*K per dot);
  2. collectives appear in ``compiled.as_text()`` with per-shard shapes and
     parseable replica groups;
  3. the probe extrapolation is exact for a linear-in-depth model.

Runs in a subprocess with 8 forced host devices.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
B, S, H, D = 8, 256, 8, 64
sh = NamedSharding(mesh, P("data", None, "model", None))

def f(q, k):
    return jnp.einsum("bshd,bthd->bhst", q, k)

c = jax.jit(f, in_shardings=(sh, sh)).lower(
    jax.ShapeDtypeStruct((B, S, H, D), jnp.float32),
    jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)).compile()
flops = c.cost_analysis()["flops"]
analytic_per_dev = 2 * B * S * S * H * D / 8
assert abs(flops / analytic_per_dev - 1) < 0.05, (flops, analytic_per_dev)

# 2: collectives parse from a program that must all-reduce
def g(x, w):
    return jnp.einsum("bd,df->bf", x, w)  # contraction dim sharded -> AR

xs = NamedSharding(mesh, P("data", "model"))
ws = NamedSharding(mesh, P("model", None))
c2 = jax.jit(g, in_shardings=(xs, ws)).lower(
    jax.ShapeDtypeStruct((16, 64), jnp.float32),
    jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
import sys
sys.path.insert(0, "src")
from repro.roofline.analysis import parse_collectives
colls = parse_collectives(c2.as_text())
assert any(op in ("all-reduce", "reduce-scatter") for op, *_ in colls), colls

# 3: probe extrapolation exact on a depth-linear scan model
def stack(depth):
    def fn(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws, unroll=True)
        return x
    return fn

def cost(depth):
    c = jax.jit(stack(depth)).lower(
        jax.ShapeDtypeStruct((32, 128), jnp.float32),
        jax.ShapeDtypeStruct((depth, 128, 128), jnp.float32)).compile()
    return c.cost_analysis()["flops"]

f2, f3 = cost(2), cost(3)
C = f3 - f2
pred10 = f2 + 8 * C
assert abs(pred10 / cost(10) - 1) < 0.02, (pred10, cost(10))
print("ROOFLINE_OK")
"""


@pytest.mark.slow
def test_roofline_assumptions_hold():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "ROOFLINE_OK" in out.stdout
