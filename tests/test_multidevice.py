"""Multi-device integration: real sharded execution on 8 host devices.

Runs in a subprocess so the forced device count never leaks into the other
tests (the dry-run rule: only dedicated processes override device count).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.configs.shapes import Shape, concrete_batch
from repro.launch import mesh as mesh_lib
from repro.models import sharding
from repro.models.lm import LM
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sharding.set_context(mesh, mesh_lib.bindings(False))

cfg = smoke_config("internlm2_1_8b")
model = LM(cfg)
params, specs = model.init(jax.random.PRNGKey(0))
param_sh = sharding.physical_shardings(specs, params)
params = jax.device_put(params, param_sh)
opt = init_opt_state(params)
batch = concrete_batch(cfg, Shape("s", 32, 4, "train"))

step = jax.jit(make_train_step(model, AdamWConfig(warmup_steps=2)),
               in_shardings=(param_sh, None, None),
               out_shardings=(param_sh, None, None))
with mesh:
    p2, o2, m = step(params, opt, batch)
loss_sharded = float(m["loss"])

# same step on 1 logical device (no constraints) must agree closely
sharding.set_context(None, {})
p2_ref, o2_ref, m_ref = jax.jit(
    make_train_step(model, AdamWConfig(warmup_steps=2)))(params, opt, batch)
loss_ref = float(m_ref["loss"])
assert abs(loss_sharded - loss_ref) < 1e-3 * max(1.0, abs(loss_ref)), \
    (loss_sharded, loss_ref)
diff = max(float(jnp.abs(a - b).max()) for a, b in
           zip(jax.tree.leaves(p2), jax.tree.leaves(p2_ref)))
assert diff < 2e-2, diff

# buddy roll on a sharded array lowers to a real cross-device permute
x = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                   NamedSharding(mesh, P(("data", "model"), None)))
rolled = jax.jit(lambda v: jnp.roll(v, 4, axis=0))(x)
np.testing.assert_array_equal(np.asarray(rolled),
                              np.roll(np.arange(32.0).reshape(8, 4), 4, 0))
print("MULTIDEVICE_OK", loss_sharded, diff)
"""


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=".",
                         env=env, capture_output=True, text=True,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIDEVICE_OK" in out.stdout
