"""Chunked-overlap collectives + MoE dispatch variants (multi-device)."""

from repro.core.overlap import overlap_efficiency
from tests.conftest import run_subprocess


def test_overlap_efficiency_model():
    # comm almost fully hidden when compute >> comm (one chunk exposed)
    assert overlap_efficiency(10.0, 1.0, 8) >= 0.875
    assert overlap_efficiency(10.0, 1.0, 32) > 0.95
    # one chunk exposed when comm ~ compute
    assert 0.8 < overlap_efficiency(1.0, 1.0, 8) < 1.0
    # comm-dominated: masking limited by compute available
    assert overlap_efficiency(0.1, 1.0, 8) < 0.3
    # monolithic baseline floor
    assert overlap_efficiency(1.0, 1.0, 1, masking_floor=0.6) == 0.6


def test_collective_matmul_matches_plain():
    run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.overlap import collective_matmul_allgather
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.3
w = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.3
fn = jax.shard_map(lambda xl, wl: collective_matmul_allgather(xl, wl, axis_name="model"),
               mesh=mesh, in_specs=(P("model", None), P(None, None)),
               out_specs=P(None, None), check_vma=False)
got = fn(x, w)
want = x @ w
assert float(jnp.abs(got - want).max()) < 1e-4
print("CM-OK")
""", devices=4)


def test_moe_dp_local_matches_gshard():
    """dp_local (weights move, not tokens) == GShard with no-drop capacity."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.core.meshctx import use_mesh
from repro.models import moe as moe_mod
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("deepseek-moe-16b").reduced()
cfg = dataclasses.replace(cfg, dtype="float32", moe=dataclasses.replace(
    cfg.moe, capacity_factor=16.0, num_experts=4))
p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model),
                      jnp.float32) * 0.3

def f(dispatch):
    def g(p, x):
        with use_mesh(mesh):
            y, _ = moe_mod.moe_forward(p, x, cfg, dispatch=dispatch)
        return y
    return jax.jit(g)(p, x)

y_ref, _ = moe_mod.moe_forward(p, x, cfg, dispatch="gshard")
y_dp = f("dp_local")
err = float(jnp.abs(y_dp - y_ref).max())
assert err < 1e-3, err

# gradients flow through the shard_map path
def loss(p):
    with use_mesh(mesh):
        y, _ = moe_mod.moe_forward(p, x, cfg, dispatch="dp_local")
    return jnp.sum(y ** 2)
g = jax.jit(jax.grad(loss))(p)
for leaf in jax.tree.leaves(g):
    assert jnp.isfinite(leaf).all()
assert float(jnp.abs(g["w_gate"]).max()) > 0
print("DP-LOCAL-OK", err)
""", devices=8, timeout=1200)
