"""Multi-device features on a 4-device placeholder mesh (subprocess):
elastic checkpoint re-shard, shard_map exact psum, int8 compressed psum,
and sharded train-step integration."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# ---------------- elastic checkpoint re-shard ----------------
from repro.checkpoint import CheckpointManager
import tempfile

tmp = tempfile.mkdtemp()
mgr = CheckpointManager(tmp)
tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
mgr.save(3, tree)                      # written from replicated layout

mesh = jax.make_mesh((2, 2), ("data", "model"))
sh = {"w": NamedSharding(mesh, P("data", "model"))}
out = mgr.restore(3, tree, shardings=sh)
assert out["w"].sharding == sh["w"], out["w"].sharding
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
print("OK elastic-reshard")

# ---------------- exact psum inside shard_map ----------------
from jax import shard_map
from repro.exact import exact_psum

dmesh = jax.make_mesh((4,), ("data",))
x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 16)),
                jnp.float32)

def f(xs):
    return exact_psum(xs[0], "data")

got = shard_map(f, mesh=dmesh, in_specs=P("data", None),
                out_specs=P(), check_vma=False)(x)
# exact sum must be permutation-invariant: compare against a permuted
# device order by rolling shards
got2 = shard_map(f, mesh=dmesh, in_specs=P("data", None),
                 out_specs=P(), check_vma=False)(jnp.roll(x, 1, axis=0))
np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))
ref = np.sum(np.asarray(x, np.float64), axis=0)
np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)
print("OK exact-psum")

# ---------------- int8 compressed psum w/ error feedback ----------------
from repro.optim.compress import compressed_psum, init_error

g = jnp.asarray(np.random.default_rng(1).standard_normal((4, 8, 32)),
                jnp.float32)

def step(gs):
    grads = {"g": gs[0]}
    err = init_error(grads)
    out, new_err = compressed_psum(grads, err, "data")
    return out["g"], new_err["g"][None]     # restore leading shard axis

avg, err = shard_map(step, mesh=dmesh, in_specs=P("data", None, None),
                     out_specs=(P(), P("data", None, None)),
                     check_vma=False)(g)
true_avg = np.mean(np.asarray(g, np.float64), axis=0)
rel = np.linalg.norm(np.asarray(avg) - true_avg) / np.linalg.norm(true_avg)
assert rel < 0.05, rel
assert float(jnp.abs(err).max()) > 0       # residual captured
print("OK compressed-psum", rel)

# ---------------- sharded end-to-end train step ----------------
from repro.configs import get_config
from repro.models import build_model
from repro.optim import AdamWConfig, init_state
from repro.runtime import make_train_step
from repro.data import DataConfig, PatternLM, device_batch

cfg = get_config("qwen3-32b", smoke=True)
model = build_model(cfg)
step_fn = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=0),
                          mesh)
params = model.init(jax.random.PRNGKey(0))
pspecs = model.param_specs(mesh)
params = jax.tree_util.tree_map(
    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspecs)
opt = init_state(params)
src = PatternLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                           global_batch=4, source="pattern"))
losses = []
for i in range(4):
    batch = device_batch(src.batch_at(i), mesh)
    params, opt, stats = step_fn(params, opt, batch)
    losses.append(float(stats["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0]
print("OK sharded-train", [round(l, 3) for l in losses])
print("ALLOK")
"""


def test_distributed_features():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALLOK" in out.stdout, out.stdout
