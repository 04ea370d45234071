"""Multi-device semantics (8 fake CPU devices via subprocess — jax fixes the
device count at first init, so these can't run in the main test process).

Covers: distributed gossip-MC == single-device full-GD; gossip-DP LM
training consensus + parity with exact all-reduce DP; MoE expert
parallelism == single-program MoE; sharded train step runs on a
multi-pod mesh.
"""

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.distributed, pytest.mark.slow]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_prog(prog: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_gossip_mc_distributed_matches_single_device():
    run_prog("""
import jax, jax.numpy as jnp
from repro.mesh import build_mesh as make_mesh
from repro.config import GossipMCConfig
from repro.core import grid as G, gossip, waves, objective as obj
from repro.core.state import make_problem, init_state
from repro.data import lowrank_problem
cfg = GossipMCConfig(m=160, n=160, p=4, q=2, rank=4)
spec = G.GridSpec(cfg.m, cfg.n, cfg.p, cfg.q, cfg.rank)
ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=0.4, seed=0)
prob = make_problem(ds.x, ds.train_mask, spec)
st0 = init_state(jax.random.PRNGKey(1), spec)
mesh = make_mesh((4, 2), ("data", "model"))
step, _ = gossip.make_gossip_step(mesh, (cfg.p, cfg.q), cfg, steps_per_call=300)
carry = gossip.init_carry(st0)
carry = step(prob, carry)
st = st0
for _ in range(300):
    st = waves.full_gradient_step(prob, st, rho=cfg.rho, lam=cfg.lam, a=cfg.a, b=cfg.b)
diff = float(jnp.max(jnp.abs(carry.state.U - st.U)))
assert diff < 1e-5, diff
c = float(gossip.distributed_cost(mesh, prob, carry.state, cfg.lam))
c0 = float(obj.total_report_cost(prob.xb, prob.maskb, st.U, st.W, cfg.lam))
assert abs(c - c0) / max(c0, 1e-9) < 1e-4, (c, c0)
print("OK", diff)
""")


def test_gossip_mc_sparse_layout_matches_dense_full_gd():
    run_prog("""
import jax, jax.numpy as jnp
from repro.mesh import build_mesh as make_mesh
from repro.config import GossipMCConfig
from repro.core import grid as G, gossip, waves, objective as obj
from repro.core.state import make_problem, init_state
from repro.data import lowrank_problem
from repro import sparse
cfg = GossipMCConfig(m=160, n=160, p=4, q=2, rank=4)
spec = G.GridSpec(cfg.m, cfg.n, cfg.p, cfg.q, cfg.rank)
ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=0.4, seed=0)
prob = make_problem(ds.x, ds.train_mask, spec)
sp = sparse.from_blocks(prob.xb, prob.maskb)
st0 = init_state(jax.random.PRNGKey(1), spec)
mesh = make_mesh((4, 2), ("data", "model"))
step, _ = gossip.make_gossip_step(mesh, (cfg.p, cfg.q), cfg,
                                  steps_per_call=100, layout="sparse")
carry = gossip.init_carry(st0)
carry = step(sp, carry)
st = st0
for _ in range(100):
    st = waves.full_gradient_step(prob, st, rho=cfg.rho, lam=cfg.lam, a=cfg.a, b=cfg.b)
diff = float(jnp.max(jnp.abs(carry.state.U - st.U)))
assert diff < 1e-5, diff
c = float(gossip.distributed_cost(mesh, sp, carry.state, cfg.lam))
c0 = float(obj.total_cost(prob, st.U, st.W, cfg.lam))
assert abs(c - c0) / max(c0, 1e-9) < 1e-4, (c, c0)
print("OK", diff)
""")


def test_gossip_mc_staleness_and_compression_still_converge():
    run_prog("""
import jax, jax.numpy as jnp
from repro.mesh import build_mesh as make_mesh
from repro.config import GossipMCConfig
from repro.core import grid as G, gossip
from repro.core.state import make_problem, init_state
from repro.data import lowrank_problem
cfg = GossipMCConfig(m=160, n=160, p=4, q=2, rank=4)
spec = G.GridSpec(cfg.m, cfg.n, cfg.p, cfg.q, cfg.rank)
ds = lowrank_problem(cfg.m, cfg.n, cfg.rank, density=0.4, seed=0)
prob = make_problem(ds.x, ds.train_mask, spec)
st0 = init_state(jax.random.PRNGKey(1), spec)
mesh = make_mesh((4, 2), ("data", "model"))
base = None
for kw in [{}, dict(staleness=4), dict(compression="int8"), dict(compression="topk")]:
    step, _ = gossip.make_gossip_step(mesh, (cfg.p, cfg.q), cfg, steps_per_call=400, **kw)
    carry = gossip.init_carry(st0)
    carry = step(prob, carry)
    c = float(gossip.distributed_cost(mesh, prob, carry.state, cfg.lam))
    if base is None:
        base = c
    assert c < 5e4, (kw, c)     # all variants make strong progress
print("OK", base)
""")


def test_gossip_dp_lm_training_matches_allreduce():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from repro.mesh import build_mesh as make_mesh
from repro.config import get_smoke_config, TrainConfig
from repro.models import build_model
from repro.models.api import Ctx
from repro.optim import make_optimizer
from repro.optim.optimizers import apply_updates
from repro.train.gossip_dp import (make_gossip_dp_step, replicate_for_workers,
                                   consensus_error)
cfg = get_smoke_config("internlm2-20b")
model = build_model(cfg, Ctx(attn_impl="ref", cache_dtype=jnp.float32))
tc = TrainConfig(optimizer="sgd", learning_rate=1e-2, warmup_steps=0,
                 total_steps=100, max_grad_norm=0.0)
opt = make_optimizer(tc)
mesh = make_mesh((8,), ("data",))
loss_fn = lambda p, b: model.loss(p, b)
gstep = make_gossip_dp_step(loss_fn, opt, mesh)
params = model.init(jax.random.PRNGKey(0))
opt_state = opt.init(params)
B, L = 16, 16
def batch_at(i):
    k = jax.random.PRNGKey(100 + i)
    toks = jax.random.randint(k, (B, L), 0, cfg.vocab_size)
    return {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}

# gossip-DP
gp = replicate_for_workers(params, 8)
go = replicate_for_workers(opt_state, 8)
for i in range(10):
    gp, go, gloss = gstep(gp, go, batch_at(i), jnp.int32(i))
cerr = float(consensus_error(gp))
# exact all-reduce DP (single device, same global batch)
@jax.jit
def astep(p, o, b):
    loss, g = jax.value_and_grad(loss_fn)(p, b)
    u, o = opt.update(g, o, p)
    return apply_updates(p, u), o, loss
ap, ao = params, opt_state
for i in range(10):
    ap, ao, aloss = astep(ap, ao, batch_at(i))
print("consensus err:", cerr, "losses:", float(gloss), float(aloss))
assert cerr < 0.05, cerr                       # workers agree
assert abs(float(gloss) - float(aloss)) < 0.15 * abs(float(aloss))
""")


def test_moe_ep_matches_single_program():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.mesh import build_mesh as make_mesh
from repro.config import MoEConfig
from repro.models import moe as MOE
cfg = MoEConfig(num_experts=8, num_experts_per_tok=2, expert_d_ff=32)
d = 64
mesh = make_mesh((2, 4), ("data", "model"))
params = MOE.init_moe(jax.random.PRNGKey(0), d, cfg, jnp.float32, pad_to=4)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))
y_ref, aux_ref = MOE.moe_ffn(params, x, cfg)
y_ep, aux_ep = jax.jit(lambda p, xx: MOE.moe_ffn(
    p, xx, cfg, ep_axis="model", mesh=mesh, dp=("data",)))(params, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=2e-4,
                           atol=2e-5)
# the balance loss is a nonlinear function of per-shard token means, so the
# sharded value only approximates the global one (standard for prod MoE)
np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=0.2)
print("OK")
""")


def test_moe_a2a_dispatch_matches_single_program():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.mesh import build_mesh as make_mesh
from repro.config import MoEConfig
from repro.models import moe as MOE
cfg = MoEConfig(num_experts=8, num_experts_per_tok=2, expert_d_ff=32)
d = 64
mesh = make_mesh((2, 4), ("data", "model"))
params = MOE.init_moe(jax.random.PRNGKey(0), d, cfg, jnp.float32, pad_to=4)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))
y_ref, _ = MOE.moe_ffn(params, x, cfg)
# capacity ≥ all slots -> zero drops -> exact match
y_a2a, _ = jax.jit(lambda p, xx: MOE.moe_ffn(
    p, xx, cfg, ep_axis="model", mesh=mesh, dp=("data",), impl="a2a",
    a2a_capacity_factor=4.0))(params, x)
np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref), rtol=2e-4,
                           atol=2e-5)
# default capacity: a few drops allowed, bulk must match
y_d, _ = jax.jit(lambda p, xx: MOE.moe_ffn(
    p, xx, cfg, ep_axis="model", mesh=mesh, dp=("data",), impl="a2a"))(params, x)
diff = np.abs(np.asarray(y_d) - np.asarray(y_ref))
frac_off = float((diff.max(-1) > 1e-3).mean())
assert frac_off < 0.08, frac_off
print("OK frac_off", frac_off)
""")


def test_train_step_multipod_mesh_runs_and_improves():
    run_prog("""
import jax, jax.numpy as jnp
from repro.mesh import build_mesh as make_mesh
from repro.config import get_smoke_config, ShapeConfig, TrainConfig
from repro.models import build_model
from repro.models.api import Ctx
from repro.train.step import make_train_step
from repro.launch.mesh import mesh_config_for
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
mesh_cfg = mesh_config_for(mesh, multi_pod=True, fsdp=True)
cfg = get_smoke_config("gemma2-2b")
ctx = Ctx(attn_impl="ref", cache_dtype=jnp.float32, mesh=mesh,
          dp=("pod", "data"))
model = build_model(cfg, ctx)
shape = ShapeConfig("t", 32, 8, "train")
step, info = make_train_step(model, mesh, mesh_cfg, shape,
                             TrainConfig(learning_rate=1e-3, warmup_steps=0))
params = jax.device_put(model.init(jax.random.PRNGKey(0)), info["params"])
opt = jax.device_put(info["optimizer"].init(params), info["opt"])
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "targets": jnp.ones((8, 32), jnp.int32)}
batch = jax.device_put(batch, info["batch"])
losses = []
for _ in range(8):
    params, opt, m = step(params, opt, batch)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("OK", losses[0], "->", losses[-1])
""")
