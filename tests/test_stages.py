"""The ZO step's stages (``obs.stage``) name the compiled program's ops.

Under jit a stage is a ``jax.named_scope``: its name lands in the
``op_name`` metadata of every op traced inside it, which is what a TPU
device trace reports as each op's ``tf_op``.  Eagerly the same call is
the installed tracer's span.  These tests check both on the CPU at the
smoke size: every stage an eager step records appears in the compiled
step's metadata, for each estimator and backend the trainer can run.
"""
import codecs
import glob
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import api, estimators, obs  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

B, S = 2, 16

# spec overrides -> the stages one step opens, eagerly and compiled
CASES = {
    "two_point-scan": ({"runtime.backend": "scan"},
                       {obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS,
                        obs.LOSS_HEAD, obs.UPDATE}),
    "two_point-dense": ({"runtime.backend": "dense"},
                        {obs.PERTURB, obs.FWD_PLUS, obs.FWD_MINUS,
                         obs.LOSS_HEAD, obs.UPDATE}),
    "virtual-paired": ({"runtime.forward_backend": "virtual_ref",
                        "runtime.paired_probes": True},
                       {obs.FWD_PAIR, obs.LOSS_HEAD, obs.UPDATE}),
    "one_sided": ({"estimator.name": "one_sided", "estimator.q": 2},
                  {obs.FWD_BASE, obs.FWD_PLUS, obs.LOSS_HEAD, obs.UPDATE}),
}


def _trainer(overrides):
    spec = api.with_overrides(api.preset("lezo-opt13b"), {
        "model.variant": "smoke", "model.seq_len": S, "run.batch_size": B,
        "run.eval_every": 0, **overrides})
    return Trainer.from_spec(spec)


def _batch():
    tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % 97
    return {"tokens": tokens, "labels": (tokens + 1) % 97,
            "loss_mask": jnp.ones((B, S), jnp.float32)}


def _eager_stages(trainer, batch):
    """Names of the spans one un-jitted step records."""
    step, _ = estimators.make_step(trainer.loss_fn, trainer.spec,
                                   trainer.est_cfg)
    ring = obs.RingSink()
    with obs.use(obs.Tracer(sinks=[ring])):
        step(trainer.trainable, trainer.est_state, batch, jnp.int32(0),
             jnp.uint32(1))
    return {r.name for r in ring.records()}


def _op_name_parts(hlo_text):
    """Every scope name on the ``op_name`` paths of an HLO module's text
    (the printer escapes non-ASCII bytes as octal; XLA joins the paths of
    merged ops with ``;``)."""
    parts = set()
    for raw in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', hlo_text):
        path = codecs.escape_decode(raw.encode("ascii"))[0].decode("utf-8")
        parts.update(re.split(r"[/;]", path))
    return parts


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_step_names_every_eager_stage(case):
    overrides, want = CASES[case]
    trainer = _trainer(overrides)
    batch = _batch()
    eager = _eager_stages(trainer, batch)
    assert eager == want
    hlo = trainer._step.lower(trainer.trainable, trainer.est_state, batch,
                              jnp.int32(0), jnp.uint32(1)).compile().as_text()
    missing = eager - _op_name_parts(hlo)
    assert not missing, f"stages absent from the compiled step: {missing}"


def test_stage_names_ops_under_null_tracer():
    assert obs.get_tracer() is obs.NULL

    def f(x):
        with obs.stage(obs.PERTURB) as sp:
            y = sp.fence(jnp.sin(x) * 2.0)
        with obs.stage(obs.UPDATE):
            return y + 1.0

    hlo = jax.jit(f).lower(jnp.ones(8)).as_text(debug_info=True)
    assert f"/{obs.PERTURB}/" in hlo and f"/{obs.UPDATE}/" in hlo


def test_stage_under_null_is_the_shared_span():
    s1 = obs.stage(obs.PERTURB)
    s2 = obs.stage(obs.LOSS_HEAD)
    assert s1 is s2 is obs.NULL.span("anything")
    with s1 as s:
        assert s.fence("x") == "x"


def test_stage_eagerly_is_the_tracer_span():
    ring = obs.RingSink()
    with obs.use(obs.Tracer(sinks=[ring])):
        with obs.stage(obs.PERTURB) as sp:
            sp.fence(jnp.ones(3) * 2)
    assert [r.name for r in ring.records()] == [obs.PERTURB]


def test_enabled_span_lands_on_the_profiler_host_plane(tmp_path):
    tr = obs.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span(obs.TRAIN_STEP) as sp:
            sp.fence(jnp.ones(4).sum())
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    with open(path, "rb") as f:
        data = f.read()
    assert (obs.trace.ANNOTATION_PREFIX + obs.TRAIN_STEP).encode() in data
