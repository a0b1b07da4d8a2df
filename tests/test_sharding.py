"""Sharding rules + analysis unit tests (mesh-free where possible; a
subprocess runs a real 64-device dry-run cell)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import ctx, sharding
from repro.launch import specs
from repro.launch.analysis import HloCost


class FakeMesh:
    """Shape-only stand-in so rules are testable without devices."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_attention_rules():
    cfg = configs.get("internlm2-1.8b")
    assert sharding.param_pspec(cfg, "stages/s0/b0/mix/wq", (24, 2048, 2048),
                                MESH) == P(None, None, "model")
    assert sharding.param_pspec(cfg, "stages/s0/b0/mix/wo", (24, 2048, 2048),
                                MESH) == P(None, "model", None)
    assert sharding.param_pspec(cfg, "stages/s0/b0/mix/norm/scale", (24, 2048),
                                MESH) == P(None, None)


def test_vocab_sharding():
    cfg = configs.get("internlm2-1.8b")
    assert sharding.param_pspec(cfg, "embed/tok", (92544, 2048), MESH) \
        == P("model", None)
    assert sharding.param_pspec(cfg, "head/w", (2048, 92544), MESH) \
        == P(None, "model")


def test_indivisible_falls_back_to_replication():
    cfg = configs.get("internlm2-1.8b")
    # 7 doesn't divide by 16
    assert sharding.param_pspec(cfg, "stages/s0/b0/mix/wq", (24, 2048, 7),
                                MESH) == P(None, None, None)


def test_lstm_blocks_replicated():
    cfg = configs.get("xlstm-350m")
    assert sharding.param_pspec(cfg, "stages/s0/b0/mix/wq", (3, 2048, 2048),
                                MESH) == P(None, None, None)


def test_cache_seq_sharding():
    spec = sharding.cache_pspec("s0/b0/k", (24, 128, 32768, 8, 128), MESH)
    assert spec == P(None, ("data",), "model", None, None)
    # batch=1 long-context: batch dim replicated
    spec = sharding.cache_pspec("s0/b0/ckv", (26, 1, 524288, 512), MP)
    assert spec[1] is None and spec[2] == "model"


def test_batch_pspec_fallback():
    assert sharding.data_pspec((256, 4096), MESH) == P(("data",), None)
    assert sharding.data_pspec((1, 4096), MESH) == P(None, None)
    assert sharding.data_pspec((256, 4096), MP) == P(("pod", "data"), None)


def test_constrain_noop_without_mesh():
    ctx.set_mesh(None)
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, "batch", "model") is x


def test_hlo_cost_scan_multiplier():
    W = jnp.zeros((8, 64, 64))

    def f(x):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, W)[0]
    txt = jax.jit(f).lower(jnp.zeros((4, 64))).compile().as_text()
    c = HloCost(txt).total()
    assert abs(c.flops - 2 * 4 * 64 * 64 * 8) / (2 * 4 * 64 * 64 * 8) < 0.01


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """Real lower+compile of one small arch cell on a 64-device host mesh
    (subprocess so the device-count env doesn't leak into this process)."""
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=64';"
        "import sys; sys.path.insert(0, 'src');"
        "import jax, jax.numpy as jnp;"
        "from repro.launch import specs;"
        "from repro.launch.mesh import make_mesh;"
        "from repro import configs;"
        "from repro.configs.shapes import SHAPES;"
        "cfg = configs.get('granite-moe-1b-a400m');"
        "mesh = make_mesh((8, 8), ('data', 'model'));"
        "sf, ps = specs.build_train_step(cfg, mesh, 'optimized');"
        "ins = specs.input_specs(cfg, SHAPES['train_4k']);"
        "f = sf(ins['batch']);"
        "l = f.lower(ps, ins['batch'], jax.ShapeDtypeStruct((), jnp.int32),"
        "            jax.ShapeDtypeStruct((), jnp.uint32));"
        "c = l.compile();"
        "assert c.memory_analysis() is not None;"
        "print('OK')"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=".", timeout=500)
    assert "OK" in r.stdout, r.stderr[-2000:]
