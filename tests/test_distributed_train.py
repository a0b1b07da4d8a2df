"""End-to-end data-parallel ZO training on a real multi-device mesh.

Runs in a subprocess with 8 host devices: trains the same tiny model
(same seeds) on a 1-device setup and on a (4 data x 2 model) mesh and
asserts the loss trajectories match — the distributed LeZO step is
*semantically identical* to the single-device one (z is seed-derived per
element, losses all-reduce inside the jit).  This is the runnability
proof for the DP story: the only cross-replica values are scalars.
"""
import subprocess
import sys

import pytest

_CODE = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys
sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import opt
from repro.core import zo, rng
from repro.data import synthetic
from repro.distributed import ctx, sharding
from repro.launch.mesh import make_mesh
from repro.models import lm

mcfg = opt.opt_tiny(layers=2, d_model=64, vocab=256)
task = synthetic.TaskConfig(vocab=256, seq_len=48, n_classes=2)
data = synthetic.make_dataset(task, 256)
params = lm.init_params(mcfg, jax.random.PRNGKey(0))
spec = zo.build_spec(params, lm.zo_group_fn)
zcfg = zo.ZOConfig(eps=1e-3, lr=2e-4, n_drop=1)
loss_fn = lambda p, b: lm.lm_loss(mcfg, p, b)
base_seed = jnp.uint32(rng.fold_py(0, 0xC0FFEE))

def run(mesh):
    if mesh is not None:
        ctx.set_mesh(mesh)
        p_sh = sharding.params_sharding(mcfg, params, mesh)
        scal = NamedSharding(mesh, P())
        step = zo.make_zo_step(loss_fn, spec, zcfg)
        bshape = {k: jnp.asarray(v[:16]) for k, v in data.items()
                  if k != 'class_labels'}
        b_sh = sharding.batch_sharding(bshape, mesh)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh, scal, scal),
                     out_shardings=(p_sh, None))
        p = jax.device_put(params, p_sh)
    else:
        ctx.set_mesh(None)
        fn = jax.jit(zo.make_zo_step(loss_fn, spec, zcfg))
        p = params
    losses = []
    for t, batch in enumerate(synthetic.batches(data, 16, 12, seed=7)):
        b = {k: jnp.asarray(v) for k, v in batch.items()
             if k != 'class_labels'}
        if mesh is not None:
            b = jax.device_put(b, sharding.batch_sharding(b, mesh))
        p, m = fn(p, b, jnp.int32(t), base_seed)
        losses.append(float(m['loss']))
    return losses, jax.tree.map(np.asarray, p)

l1, p1 = run(None)
mesh = make_mesh((4, 2), ('data', 'model'))
l2, p2 = run(mesh)
d_loss = max(abs(a - b) for a, b in zip(l1, l2))
d_par = max(float(np.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print(f'loss_diff={d_loss:.2e} param_diff={d_par:.2e}')
assert d_loss < 1e-4, (l1, l2)
assert d_par < 1e-4
print('OK')
"""


@pytest.mark.slow
def test_dp_tp_training_matches_single_device():
    r = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                       text=True, cwd=".", timeout=500)
    assert "OK" in r.stdout, (r.stdout[-1000:], r.stderr[-2000:])
