"""Distributed checks executed in a subprocess with 8 host devices.

Run directly:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
               PYTHONPATH=src python tests/distributed_worker.py

Prints one JSON object; test_distributed.py asserts on it.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.configs import get_smoke_config
from repro.configs.base import AnalogParams, ApproxConfig, Backend, TrainConfig, TrainMode
from repro.data import SyntheticLM
from repro.launch.mesh import make_debug_mesh
from repro.models import build_model
from repro.optim.compress import crosspod_reduce, init_compression_state, int8_allreduce
from repro.runtime import sharding as shard_lib
from repro.training import steps as step_lib

results = {}

# ---------------------------------------------------------------------------
# 1. sharded train step on a (2 data x 2 model) mesh
# ---------------------------------------------------------------------------
mesh = make_debug_mesh(2, 2)
cfg = get_smoke_config("yi-6b")
model = build_model(cfg)
approx = ApproxConfig(
    backend=Backend.ANALOG, mode=TrainMode.INJECT, analog=AnalogParams(array_size=16)
)
tcfg = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-3, fsdp=True)

state = step_lib.init_train_state(model, jax.random.PRNGKey(0), approx)
state_sh = {
    "params": shard_lib.params_shardings(state["params"], mesh, tcfg.fsdp),
    "opt": {
        "m": shard_lib.params_shardings(state["opt"]["m"], mesh, True),
        "v": shard_lib.params_shardings(state["opt"]["v"], mesh, True),
        "master": shard_lib.params_shardings(state["opt"]["master"], mesh, True),
        "count": shard_lib.replicated(mesh),
    },
    "calib": jax.tree_util.tree_map(lambda _: shard_lib.replicated(mesh), state["calib"]),
    "step": shard_lib.replicated(mesh),
}
state = jax.tree_util.tree_map(jax.device_put, state, state_sh)
data = SyntheticLM(cfg.vocab_size, 16, 4, seed=1)
batch = data.batch_at(0)
batch = {
    k: jax.device_put(v, NamedSharding(mesh, shard_lib.batch_spec(v.shape, mesh)))
    for k, v in batch.items()
}
with jax.set_mesh(mesh):
    step = jax.jit(step_lib.make_train_step(model, approx, tcfg))
    losses = []
    for s in range(3):
        state, met = step(state, batch, jax.random.PRNGKey(s))
        losses.append(float(met["loss"]))
results["sharded_train_losses"] = losses
results["sharded_train_finite"] = all(np.isfinite(l) for l in losses)

# a weight that should actually be sharded over model axis
wq = state["params"]["layers"][0]["attn"]["wq"] if isinstance(state["params"]["layers"], list) else None
leaf = state["params"]["layers"]["attn"]["wq"]
results["wq_sharding"] = str(leaf.sharding.spec)
results["wq_is_sharded"] = "model" in str(leaf.sharding.spec)

# ---------------------------------------------------------------------------
# 2. elastic restore: checkpoint from (2,2), restore onto (4,2)
# ---------------------------------------------------------------------------
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(3, state, blocking=True)
    mesh2 = make_debug_mesh(4, 2)
    sh2 = {
        "params": shard_lib.params_shardings(state["params"], mesh2, True),
        "opt": {
            "m": shard_lib.params_shardings(state["opt"]["m"], mesh2, True),
            "v": shard_lib.params_shardings(state["opt"]["v"], mesh2, True),
            "master": shard_lib.params_shardings(state["opt"]["master"], mesh2, True),
            "count": shard_lib.replicated(mesh2),
        },
        "calib": jax.tree_util.tree_map(lambda _: shard_lib.replicated(mesh2), state["calib"]),
        "step": shard_lib.replicated(mesh2),
    }
    restored = mgr.restore(state, shardings=sh2)
    a = np.asarray(jax.tree_util.tree_leaves(state["params"])[0])
    b = np.asarray(jax.tree_util.tree_leaves(restored["params"])[0])
    results["elastic_restore_equal"] = bool(np.array_equal(a, b))
    # resumed training on the NEW mesh must run
    batch2 = {
        k: jax.device_put(np.asarray(v), NamedSharding(mesh2, shard_lib.batch_spec(v.shape, mesh2)))
        for k, v in data.batch_at(4).items()
    }
    tcfg2 = TrainConfig(total_steps=10, warmup_steps=1, learning_rate=1e-3, fsdp=True)
    with jax.set_mesh(mesh2):
        step2 = jax.jit(step_lib.make_train_step(model, approx, tcfg2))
        restored, met2 = step2(restored, batch2, jax.random.PRNGKey(9))
    results["elastic_resume_loss_finite"] = bool(np.isfinite(float(met2["loss"])))

# ---------------------------------------------------------------------------
# 3. multi-pod debug mesh (2 pod x 2 data x 2 model) lower+compile
# ---------------------------------------------------------------------------
mesh3 = make_debug_mesh(2, 2, n_pod=2)
state3 = jax.eval_shape(
    lambda: step_lib.init_train_state(model, jax.random.PRNGKey(0), approx)
)
sh3 = {
    "params": shard_lib.params_shardings(state3["params"], mesh3, True),
    "opt": {
        "m": shard_lib.params_shardings(state3["opt"]["m"], mesh3, True),
        "v": shard_lib.params_shardings(state3["opt"]["v"], mesh3, True),
        "master": shard_lib.params_shardings(state3["opt"]["master"], mesh3, True),
        "count": shard_lib.replicated(mesh3),
    },
    "calib": jax.tree_util.tree_map(lambda _: shard_lib.replicated(mesh3), state3["calib"]),
    "step": shard_lib.replicated(mesh3),
}
batch3_sds = model.input_specs(8, 16)
batch3_sh = jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh3, shard_lib.batch_spec(s.shape, mesh3)), batch3_sds
)
with jax.set_mesh(mesh3):
    lowered = jax.jit(
        step_lib.make_train_step(model, approx, tcfg),
        in_shardings=(sh3, batch3_sh, shard_lib.replicated(mesh3)),
    ).lower(state3, batch3_sds, jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = lowered.compile()
results["multipod_compile_ok"] = True
results["multipod_has_collectives"] = any(
    k in compiled.as_text() for k in ("all-reduce", "all-gather", "reduce-scatter")
)

# ---------------------------------------------------------------------------
# 4. compressed cross-pod all-reduce with error feedback
# ---------------------------------------------------------------------------
pod_mesh = jax.make_mesh(
    (8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,)
)

x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))  # row i = pod i's grad


def per_pod(xl, ef):
    out, ef2 = int8_allreduce(xl[0], ef[0], "pod")
    return out[None], ef2[None]


ef = jnp.zeros((8, 64))
true_mean = x.mean(0)
errs = []
for it in range(6):
    fn = jax.shard_map(
        per_pod, mesh=pod_mesh,
        in_specs=(P("pod"), P("pod")), out_specs=(P(None), P("pod")),
        check_vma=False,
    )
    out, ef = fn(x, ef)
    errs.append(float(jnp.abs(out[0] - true_mean).max()))
results["int8_reduce_err_first"] = errs[0]
results["int8_reduce_err_small"] = errs[0] < 0.05
# error feedback keeps the *accumulated* reduction unbiased: residuals stay bounded
results["ef_bounded"] = float(jnp.abs(ef).max()) < 0.05

# pytree wrapper: identity without pod axis
g = {"w": jnp.ones((4, 4))}
g2, _ = crosspod_reduce(g, init_compression_state(g, "int8"), make_debug_mesh(2, 2), "int8")
results["crosspod_identity_no_pod_axis"] = bool(np.array_equal(np.asarray(g2["w"]), np.ones((4, 4))))

# topk path through the wrapper on the pod mesh
g3 = {"w": x}
ef3 = init_compression_state(g3, "topk:0.25")
g3r, ef3 = crosspod_reduce(g3, ef3, pod_mesh, "topk:0.25")
results["topk_runs"] = bool(np.isfinite(np.asarray(g3r["w"])).all())

print(json.dumps(results))
