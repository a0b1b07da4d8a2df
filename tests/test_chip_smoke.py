"""Off-chip guarantees of ``chip_smoke.py`` and the compile-cache helper.

The smoke itself only means something on a TPU; here we pin what it
does without one: it exits non-zero before building any model and prints
no result line, also when copied out of the checkout.  The cache helper
must leave ``JAX_COMPILATION_CACHE_DIR`` to JAX when it is set and use
``<checkout>/.jax_cache`` otherwise.
"""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

_NO_MODEL = r"""
import sys
sys.path.insert(0, {root!r})
import chip_smoke

def built_a_model(*a, **k):
    print("BUILT A MODEL")
    sys.exit(99)

chip_smoke.train_phase = built_a_model
chip_smoke.four_chips = built_a_model
chip_smoke.main([])
"""


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(kw)
    return env


def test_chip_smoke_refuses_cpu_before_any_model():
    r = subprocess.run([sys.executable, "-c", _NO_MODEL.format(root=str(ROOT))],
                       capture_output=True, text=True, timeout=300,
                       env=_env(), cwd=str(ROOT))
    assert r.returncode not in (0, 99), r.stdout + r.stderr
    assert "BUILT A MODEL" not in r.stdout
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, timeout=300, env=_env(), cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_COMPILE = r"""
import sys
sys.path.insert(0, {src!r})
from repro.launch import compile_cache
print(compile_cache.enable())
import jax, jax.numpy as jnp

def {name}(x):
    return jnp.sin(x) * {salt}

jax.jit({name}).lower(jnp.ones(7)).compile()
"""


def _entries(d, name):
    """Cache entries of the jitted function ``name`` (JAX names each
    entry ``jit_<name>-<key>-...``); other test processes may write
    entries of their own to the same directory meanwhile."""
    d = pathlib.Path(d)
    return (sorted(p.name for p in d.glob(f"jit_{name}-*"))
            if d.is_dir() else [])


def _compile(name, **env):
    r = subprocess.run(
        [sys.executable, "-c",
         _COMPILE.format(src=str(ROOT / "src"), name=name,
                         salt=f"{os.getpid()}.0")],
        capture_output=True, text=True, timeout=300,
        env=_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env))
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[0]


def test_compile_cache_honours_env_dir(tmp_path):
    mine = tmp_path / "cache"
    name = "cache_probe_env_dir"
    assert _compile(name, JAX_COMPILATION_CACHE_DIR=str(mine)) == str(mine)
    assert _entries(mine, name), \
        "no cache entry written to JAX_COMPILATION_CACHE_DIR"
    assert _entries(ROOT / ".jax_cache", name) == []


def test_compile_cache_defaults_to_checkout_dir():
    from repro.launch import compile_cache
    assert compile_cache.default_dir() == ROOT / ".jax_cache"
    name = "cache_probe_checkout_dir"
    assert _compile(name) == str(ROOT / ".jax_cache")
    assert _entries(ROOT / ".jax_cache", name)


_SCOPED = r"""
import sys
sys.path.insert(0, {src!r})
from repro.launch import compile_cache
compile_cache.enable()
import jax, jax.numpy as jnp
print(jax.config.jax_compilation_cache_include_metadata_in_key)

def {name}(x):
    with jax.named_scope({scope!r}):
        return jnp.sin(x) * {salt}

jax.jit({name}).lower(jnp.ones(7)).compile()
"""


def test_compile_cache_key_keeps_stage_names(tmp_path):
    """Two programs that differ only in a named scope (a ZO stage's
    name) are two cache entries: a cache warmed by a program without
    the stage names never serves one with them."""
    mine = tmp_path / "cache"
    name = "cache_probe_scoped"
    for scope in ("perturb", "update_axpy"):
        r = subprocess.run(
            [sys.executable, "-c",
             _SCOPED.format(src=str(ROOT / "src"), name=name, scope=scope,
                            salt=f"{os.getpid()}.0")],
            capture_output=True, text=True, timeout=300,
            env=_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                     JAX_COMPILATION_CACHE_DIR=str(mine)))
        assert r.returncode == 0, r.stderr
        assert r.stdout.split() == ["True"]
    assert len(_entries(mine, name)) == 2
