"""Distributed layer tests.

Sharding-rule resolution runs in-process (pure metadata).  Everything that
needs multiple devices runs in ONE subprocess with 8 fake CPU devices
(XLA_FLAGS must be set before jax initializes, and the main test process
must keep its single-device view for the other tests).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.compression import wire_bytes
from repro.distributed.pipeline import bubble_fraction

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# In-process: rule resolution (no devices needed — uses AbstractMesh)
# ---------------------------------------------------------------------------
def _abstract_mesh(*name_size_pairs):
    """AbstractMesh across JAX versions: the current API takes
    ``((name, size), ...)`` pairs; older releases took (shape, names)."""
    from jax.sharding import AbstractMesh
    try:
        return AbstractMesh(tuple(name_size_pairs))
    except TypeError:  # pre-0.4.36 signature
        names, sizes = zip(*name_size_pairs)
        return AbstractMesh(tuple(sizes), tuple(names))


def _mesh_16x16():
    return _abstract_mesh(("data", 16), ("model", 16))


def test_resolve_divisible_axes():
    mesh = _mesh_16x16()
    rules = {"heads": "model", "embed": None}
    spec = shd.resolve_spec(P("embed", "heads"), (1024, 4096), rules, mesh)
    assert spec == P(None, "model")


def test_resolve_indivisible_falls_back_to_replication():
    mesh = _mesh_16x16()
    rules = {"heads": "model"}
    # 3 heads (custom-encoder) cannot shard 16 ways -> replicate
    spec = shd.resolve_spec(P(None, "heads"), (200, 198), rules, mesh)
    assert spec == P()


def test_resolve_no_axis_reuse():
    mesh = _mesh_16x16()
    rules = {"a": "model", "b": "model"}
    spec = shd.resolve_spec(P("a", "b"), (64, 64), rules, mesh)
    assert spec == P("model")  # second use of 'model' dropped


def test_strategy_for_mesh_multi_pod():
    mesh = _abstract_mesh(("pod", 2), ("data", 16), ("model", 16))
    s = shd.strategy_for_mesh(mesh)
    assert s.dp_axes == ("pod", "data") and s.tp_axis == "model"


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


def test_compression_wire_bytes_save():
    n = 10_000_000
    assert wire_bytes(n, 256, compressed=True) < \
        0.7 * wire_bytes(n, 256, compressed=False)


# ---------------------------------------------------------------------------
# Subprocess: 8 fake devices
# ---------------------------------------------------------------------------
_SUBPROCESS_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from jax.sharding import AxisType

B, S = int(sys.argv[1]), int(sys.argv[2])
train_only = len(sys.argv) > 3 and sys.argv[3] == "train_only"
results = {}

# --- 1. sharded train step == single-device train step ---------------------
from repro.configs import get_config, reduced
from repro.models.model import Model
from repro.distributed import sharding as shd
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (TrainStepConfig, init_state,
                                       make_step_fn, make_train_step)

cfg = reduced(get_config("qwen1.5-0.5b"))
model = Model(cfg)
oc = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
state = init_state(model, jax.random.PRNGKey(0), oc)
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

single = jax.jit(make_step_fn(model, TrainStepConfig(optimizer=oc)))
s1, m1 = single(state, batch)

mesh = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
strategy = shd.strategy_for_mesh(mesh)
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
jitted, st_sh, b_sh = make_train_step(model, mesh, strategy,
                                      TrainStepConfig(optimizer=oc,
                                                      donate=False), specs)
state_sharded = jax.device_put(state, st_sh)
batch_sharded = jax.device_put(batch, b_sh)
s2, m2 = jitted(state_sharded, batch_sharded)
results["train_loss_diff"] = abs(float(m1["loss"]) - float(m2["loss"]))
diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
         for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params))]
results["train_param_diff"] = max(diffs)

if train_only:
    print("RESULTS" + json.dumps(results))
    sys.exit(0)

# --- 2. ring collectives == native psum ------------------------------------
from repro.distributed.collectives import ring_allreduce, ring_reduce_scatter
m8 = jax.make_mesh((8,), ("d",), (AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
f = shard_map(lambda xs: ring_reduce_scatter(xs[0], "d")[None],
              mesh=m8, in_specs=(P("d", None),), out_specs=P("d", None))
results["ring_rs_err"] = float(jnp.max(jnp.abs(f(x) - x.sum(0).reshape(8, 8))))
g = shard_map(lambda xs: ring_allreduce(xs[0], "d")[None],
              mesh=m8, in_specs=(P("d", None),), out_specs=P("d", None))
results["ring_ar_err"] = float(jnp.max(jnp.abs(
    g(x) - jnp.broadcast_to(x.sum(0, keepdims=True), x.shape))))

# --- 3. pipeline forward/grad == sequential ---------------------------------
from repro.distributed.pipeline import make_pipelined_apply
mesh_pp = jax.make_mesh((8,), ("stage",), (AxisType.Auto,))
S, D, NM, MB = 8, 16, 16, 4
ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) / jnp.sqrt(D)
bs = jax.random.normal(jax.random.PRNGKey(1), (S, D)) * 0.1
xpp = jax.random.normal(jax.random.PRNGKey(2), (NM, MB, D))
stage_fn = lambda p, h: jnp.tanh(h @ p[0] + p[1])
apply = make_pipelined_apply(stage_fn, mesh_pp, axis_name="stage")

def seq_apply(params, x):
    h = x
    for i in range(S):
        h = jnp.tanh(h @ params[0][i] + params[1][i])
    return h

results["pp_fwd_err"] = float(jnp.max(jnp.abs(
    apply((ws, bs), xpp) - seq_apply((ws, bs), xpp))))
gp = jax.grad(lambda p: jnp.sum(apply(p, xpp) ** 2))((ws, bs))
gr = jax.grad(lambda p: jnp.sum(seq_apply(p, xpp) ** 2))((ws, bs))
results["pp_grad_err"] = max(float(jnp.max(jnp.abs(a - b)))
                             for a, b in zip(jax.tree.leaves(gp),
                                             jax.tree.leaves(gr)))

# --- 4. compressed allreduce: mean + EF bias decay ---------------------------
from repro.distributed.compression import compressed_allreduce, init_ef_state
shard = 1000 // 8 + (1 if 1000 % 8 else 0)
shard = (1000 + (-1000) % 8) // 8
gs = jax.random.normal(jax.random.PRNGKey(3), (8, 1000))

def one_round(g, resid):
    f = shard_map(
        lambda gg, rr: (lambda o, s: (o[None], s.residual[None]))(
            *compressed_allreduce(gg[0], init_ef_state((shard,))._replace(
                residual=rr[0]), "d")),
        mesh=m8, in_specs=(P("d", None), P("d", None)),
        out_specs=(P("d", None), P("d", None)), check_vma=False)
    return f(g, resid)

resid = jnp.zeros((8, shard))
want = gs.mean(0)
errs = []
acc_err = jnp.zeros(1000)
for _ in range(30):
    out, resid = one_round(gs, resid)
    acc_err = acc_err + (out[0] - want)
    errs.append(float(jnp.linalg.norm(acc_err) / (jnp.linalg.norm(want) + 1e-9)))
results["ef_single_round_rel"] = errs[0]
results["ef_accum_rel_after_30"] = errs[-1] / 30.0

print("RESULTS" + json.dumps(results))
"""


def _run_subprocess(batch: int, seq: int, *extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT,
                           str(batch), str(seq), *extra],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS")][-1]
    return json.loads(line[len("RESULTS"):])


@pytest.fixture(scope="module")
def sub_results():
    # reduced default sizes; the full-size train step runs under -m slow
    return _run_subprocess(8, 9)


def test_sharded_train_step_matches_single(sub_results):
    assert sub_results["train_loss_diff"] < 1e-3
    assert sub_results["train_param_diff"] < 5e-3


@pytest.mark.slow
def test_sharded_train_step_matches_single_full_size():
    res = _run_subprocess(8, 17, "train_only")
    assert res["train_loss_diff"] < 1e-3
    assert res["train_param_diff"] < 5e-3


def test_ring_collectives(sub_results):
    assert sub_results["ring_rs_err"] < 1e-5
    assert sub_results["ring_ar_err"] < 1e-5


def test_pipeline_parallel(sub_results):
    assert sub_results["pp_fwd_err"] < 1e-5
    assert sub_results["pp_grad_err"] < 1e-3


def test_error_feedback_keeps_time_average_unbiased(sub_results):
    """One int8 round is ~5% off; with error feedback the *time-averaged*
    gradient error decays ~1/T instead of staying constant."""
    assert sub_results["ef_single_round_rel"] < 0.2
    assert sub_results["ef_accum_rel_after_30"] < \
        sub_results["ef_single_round_rel"] / 3
