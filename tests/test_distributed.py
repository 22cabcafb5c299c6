"""Multi-device behaviors, run in subprocesses (the 8-device XLA flag must
not leak into this test process): sharded training step, elastic
checkpoint restore across topologies, DP-only policy equivalence.
"""

import subprocess
import sys
from pathlib import Path


SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, n_devices: int = 8) -> str:
    env = {
        "PYTHONPATH": SRC,
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device(tmp_path):
    """A jitted sharded train step on an 8-device mesh must produce the
    same loss trajectory as single-device execution (same seeds)."""
    code = """
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.model import Model
from repro.models.sharding import MeshPolicy, param_specs, use_policy
from repro.data.pipeline import synthetic_batch_at

assert len(jax.devices()) == 8
cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128)
model = Model(cfg)

def losses(policy, n=4):
    with use_policy(policy):
        params = model.init(jax.random.PRNGKey(0))
        if policy.mesh is not None:
            shardings = jax.tree.map(
                lambda s: NamedSharding(policy.mesh, s),
                param_specs(params, policy))
            params = jax.tree.map(jax.device_put, params, shardings)
        @jax.jit
        def step(p, b):
            l, g = jax.value_and_grad(lambda pp: model.loss(pp, b)[0])(p)
            return l, jax.tree.map(lambda pp, gg: pp - 1e-2*gg.astype(pp.dtype), p, g)
        out = []
        for t in range(n):
            b = synthetic_batch_at(t, seed=3, batch_size=8, seq_len=16,
                                   vocab_size=cfg.vocab_size)
            l, params = step(params, b)
            out.append(float(l))
    return out

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
pol = MeshPolicy(mesh=mesh, dp=("data",), tp="model")
sharded = losses(pol)
single = losses(MeshPolicy())
np.testing.assert_allclose(sharded, single, rtol=2e-2)
print("OK", sharded[-1])
"""
    out = _run(code)
    assert "OK" in out


def test_elastic_restore_across_topologies(tmp_path):
    """Checkpoint written under an 8-device mesh restores onto a 4-device
    mesh (elastic scaling: topology-independent checkpoints)."""
    ckpt = tmp_path / "ck"
    save_code = f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.models.model import Model
from repro.models.sharding import MeshPolicy, param_specs, use_policy
from repro.train.checkpoint import save_checkpoint

cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128)
model = Model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
pol = MeshPolicy(mesh=mesh, dp=("data",), tp="model")
with use_policy(pol):
    params = model.init(jax.random.PRNGKey(7))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(params, pol))
    params = jax.tree.map(jax.device_put, params, shardings)
save_checkpoint(r"{ckpt}", 5, {{"params": params}})
print("SAVED")
"""
    _run(save_code, n_devices=8)

    restore_code = f"""
import numpy as np
import jax
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.models.model import Model
from repro.models.sharding import MeshPolicy, param_specs, use_policy
from repro.train.checkpoint import latest_checkpoint, restore_checkpoint

cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128)
model = Model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
pol = MeshPolicy(mesh=mesh, dp=("data",), tp="model")
with use_policy(pol):
    template = model.init(jax.random.PRNGKey(0))
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         param_specs(template, pol))
state, step, _ = restore_checkpoint(
    latest_checkpoint(r"{ckpt}"), {{"params": template}},
    shardings={{"params": shardings}})
assert step == 5
# same seed-7 params, now resharded on the smaller mesh
with use_policy(MeshPolicy()):
    want = Model(cfg).init(jax.random.PRNGKey(7))
for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(want)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("RESTORED on 4 devices")
"""
    out = _run(restore_code, n_devices=4)
    assert "RESTORED" in out


def test_dp_only_policy_runs():
    """The <1B-param DP-only policy (model axis folded into data) trains."""
    code = """
import jax
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.models.model import Model
from repro.models.sharding import MeshPolicy, param_specs, use_policy
from repro.data.pipeline import synthetic_batch_at

cfg = get_config("mamba2-130m").reduced()
model = Model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
pol = MeshPolicy(mesh=mesh, dp=("data", "model"), tp=None)
with use_policy(pol):
    params = model.init(jax.random.PRNGKey(0))
    @jax.jit
    def step(p, b):
        return jax.value_and_grad(lambda pp: model.loss(pp, b)[0])(p)[0]
    b = synthetic_batch_at(0, seed=0, batch_size=8, seq_len=16,
                           vocab_size=cfg.vocab_size)
    print("loss", float(step(params, b)))
"""
    out = _run(code)
    assert "loss" in out
