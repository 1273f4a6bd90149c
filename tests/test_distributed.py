"""Distributed-runtime tests on 8 forced host devices (subprocesses —
device count is frozen at first jax init, so these never run in-process).
Covers: mesh construction, sharded train step, pipeline parallelism vs
sequential, compressed cross-pod psum, sharding-rule sanity."""
import os
import subprocess
import sys

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           JAX_PLATFORMS="cpu")


def run(script: str, timeout=420):
    r = subprocess.run([sys.executable, "-c", "import sys; "
                        "sys.path.insert(0, 'src')\n" + script],
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True, env=ENV,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_mesh_and_sharded_train_step():
    out = run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config, dummy_inputs
from repro.launch import sharding as shd
from repro.launch.train import init_train_state, make_train_step
from repro.optim import AdamWConfig

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                          batch_axes=("data",), tp=2)
opt = AdamWConfig()
state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
batch = dummy_inputs(cfg, "train", batch=8, seq=32)
ssp = shd.named_shardings(shd.state_pspecs(state, mesh), mesh)
bsp = shd.named_shardings(shd.input_pspecs(
    {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()},
    mesh), mesh)
with mesh:
    state = jax.device_put(state, ssp)
    batch = jax.device_put(batch, bsp)
    step = jax.jit(make_train_step(cfg, opt), in_shardings=(ssp, bsp),
                   out_shardings=(ssp, None), donate_argnums=(0,))
    s1, m1 = step(state, batch)
    s2, m2 = step(s1, batch)
assert np.isfinite(float(m1["loss"])) and float(m2["loss"]) < float(m1["loss"]) + 1.0
print("LOSS", float(m1["loss"]), float(m2["loss"]))
""")
    assert "LOSS" in out


def test_multi_pod_mesh_axes():
    run("""
import jax
import numpy as np
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
assert mesh.axis_names == ("pod", "data", "model")
assert int(np.prod(list(mesh.shape.values()))) == 8
""")


def test_pipeline_forward_matches_sequential():
    run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.launch.mesh import make_test_mesh
from repro.launch.pipeline import pipeline_forward

mesh = make_test_mesh((8,), ("pod",))
S, M, MB, D = 8, 4, 2, 16       # 8 stages, 4 microbatches
rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.normal(size=(S, D, D)) / np.sqrt(D), jnp.float32)
x = jnp.asarray(rng.normal(size=(M, MB, D)), jnp.float32)

def stage_fn(w, h):
    return jnp.tanh(h @ w)

def pipelined(ws, xm):
    return pipeline_forward(stage_fn, ws[0], xm, axis_name="pod")

out = shard_map(pipelined, mesh=mesh,
                in_specs=(P("pod"), P()), out_specs=P())(Ws, x)
# out valid on last stage; shard_map P() output takes... replicate check:
ref = x
for s in range(S):
    ref = stage_fn(Ws[s], ref.reshape(M * MB, D).reshape(M, MB, D))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("PIPE OK")
""")


def test_compressed_psum_close_to_exact():
    run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.launch.collectives import compressed_psum
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((8,), ("pod",))
rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)

exact = shard_map(lambda a: jax.lax.psum(a, "pod"), mesh=mesh,
                  in_specs=P("pod"), out_specs=P())(x)
comp = shard_map(lambda a: compressed_psum(a, "pod"), mesh=mesh,
                 in_specs=P("pod"), out_specs=P())(x)
err = float(jnp.max(jnp.abs(exact - comp)))
scale = float(jnp.max(jnp.abs(x))) / 127
assert err <= 8 * scale + 1e-6, (err, scale)
print("PSUM OK", err)
""")


def test_overlapped_tp_matmul_matches_dense():
    run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.launch.collectives import overlapped_tp_matmul

mesh = jax.make_mesh((8,), ("model",))
rng = np.random.default_rng(2)
x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)   # sharded on k
w = jnp.asarray(rng.normal(size=(64, 24)), jnp.float32)

# every device holds the full product after the ring; jax cannot prove
# the replication statically (ppermute -> varying), so skip the vma check
out = shard_map(lambda a, b: overlapped_tp_matmul(a, b, "model"),
                mesh=mesh, in_specs=(P(None, "model"), P()),
                out_specs=P(), check_rep=False)(x, w)
# each device computes the full (16, 24) product from rotated shards
np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                           rtol=1e-4, atol=1e-4)
print("OVERLAP OK")
""")


def test_sharding_rules_divisibility_guard():
    run("""
import jax, jax.numpy as jnp
from repro.launch import sharding as shd
mesh = jax.make_mesh((4, 2), ("data", "model"))
params = {"blocks": {"attn": {"q": {"w": jnp.zeros((24, 2048, 2048))}}},
          "odd": {"w": jnp.zeros((7, 3000007))},
          "small": jnp.zeros((4,))}
specs = shd.param_pspecs(params, mesh)
q = specs["blocks"]["attn"]["q"]["w"]
assert q == jax.sharding.PartitionSpec(None, "data", "model"), q
odd = specs["odd"]["w"]
assert odd == jax.sharding.PartitionSpec(None, None), odd  # indivisible
assert specs["small"] == jax.sharding.PartitionSpec()
print("RULES OK")
""")
