"""Swarm subsystem suite (DESIGN.md §14).

Fast tier: the wire protocol (framing round trip, byte counters, EOF),
the fixed-order commit reduction (arrival-order invariance, quorum
threshold/reweighting against the in-trainer quorum math), the step
ledger's stale-epoch/stale-step/duplicate handling, the deterministic
chaos schedules, and the spec-validation constraints.

Slow tier: subprocess end-to-end — a 2-worker swarm must commit a
scalar stream AND final parameters bit-identical to the single-process
trainer on the same spec; a chaos-crashed swarm must recover through
the elastic-rejoin path without changing a committed bit; and both
chaos and quorum-degraded runs must pass ``launch replay``.
"""
import dataclasses
import json
import os
import pathlib
import socket

import numpy as np
import pytest

from repro import api
from repro.swarm import chaos as chaos_mod
from repro.swarm import commit, proto

STREAM_KEYS = ("loss", "projected_grad", "seed", "arrived", "shard_losses",
               "active_layers")


# ========================================================== wire protocol
def _conn_pair():
    a, b = socket.socketpair()
    return proto.Conn(a), proto.Conn(b)


def test_proto_roundtrip_and_counters():
    a, b = _conn_pair()
    c = proto.StepContribution(
        run_id="r1", membership_epoch=3, step=7, seed=123456789,
        shard_losses={"0": [4.25, 4.5], "2": [3.75, 4.0]}, worker_id=1)
    cm = proto.StepCommit(step=7, seed=123456789, g=-0.125, loss=4.125,
                          active_layers=2, membership_epoch=3,
                          arrived=[1, 0, 1], ckpt_worker=0)
    a.send(c.to_wire())
    a.send(cm.to_wire())
    got_c = proto.StepContribution.from_wire(b.recv(timeout=5.0))
    got_cm = proto.StepCommit.from_wire(b.recv(timeout=5.0))
    assert got_c == c
    assert got_cm == cm
    # floats survive JSON exactly (repr round trip)
    assert got_cm.g == -0.125 and got_c.shard_losses["2"] == [3.75, 4.0]
    assert a.bytes_sent == b.bytes_recv > 0
    assert b.msgs_recv == 2
    a.close()
    assert b.recv(timeout=5.0) is None      # EOF -> None, not an exception
    b.close()


def test_proto_recv_timeout_preserves_partial_frame():
    a, b = _conn_pair()
    payload = proto.encode({"type": "bye"})
    a.sock.sendall(payload[:3])             # half a length prefix
    with pytest.raises(socket.timeout):
        b.recv(timeout=0.05)
    a.sock.sendall(payload[3:])
    assert b.recv(timeout=5.0) == {"type": "bye"}
    a.close(), b.close()


def test_proto_rejects_unknown_type_and_oversized_frame():
    with pytest.raises(proto.ProtocolError):
        proto.encode({"type": "gossip"})
    a, b = _conn_pair()
    a.sock.sendall(proto._LEN.pack(proto.MAX_FRAME + 1))
    with pytest.raises(proto.ProtocolError):
        b.recv(timeout=5.0)
    a.close(), b.close()


# ===================================================== commit reduction
def test_quorum_count_matches_trainer_formula():
    for n in range(1, 9):
        for q in (0.25, 0.5, 0.75, 0.9, 1.0):
            assert commit.quorum_count(n, q) == max(1, int(round(q * n)))


def test_reduce_losses_fixed_order_left_to_right_f32():
    pairs = [(4.125, 4.0), (3.5, 3.75), (5.0, 4.875)]
    lp, lm, arrived = commit.reduce_losses(pairs)
    f = np.float32
    want_lp = f(0.0)
    for p, _ in pairs:
        want_lp = f(want_lp + f(p))
    assert lp == f(want_lp / f(3.0))
    assert arrived == [1, 1, 1]
    assert lp.dtype == np.float32 and lm.dtype == np.float32


def test_commit_is_arrival_order_invariant():
    """The ledger keys contributions by shard index, so any arrival
    permutation commits the same bits."""
    from repro.swarm.coordinator import StepLedger
    losses = {0: [4.25, 4.0], 1: [3.5, 3.75], 2: [5.0, 4.875],
              3: [4.0, 4.125]}

    def run(order):
        led = StepLedger("r", 0, 99, 1, 4)
        for wid, shard in enumerate(order):
            c = proto.StepContribution(
                run_id="r", membership_epoch=1, step=0, seed=99,
                shard_losses={str(shard): losses[shard]}, worker_id=wid)
            assert led.add(c, 1) == "ok"
        return led.commit(1e-3)

    base = run([0, 1, 2, 3])
    for order in ([3, 2, 1, 0], [1, 3, 0, 2], [2, 0, 3, 1]):
        other = run(order)
        for k in ("l_plus", "l_minus", "loss", "projected_grad"):
            assert np.float32(other[k]) == np.float32(base[k]), (order, k)
        assert other["arrived"] == base["arrived"]


def test_duplicate_contribution_overwrites_bit_identically():
    from repro.swarm.coordinator import StepLedger
    led = StepLedger("r", 0, 99, 1, 2)
    c = proto.StepContribution(run_id="r", membership_epoch=1, step=0,
                               seed=99, shard_losses={"0": [4.0, 4.25]})
    assert led.add(c, 1) == "ok"
    assert led.add(c, 1) == "ok"            # resend after a nudge
    assert led.add(dataclasses.replace(c, shard_losses={"1": [3.5, 3.0]}),
                   1) == "ok"
    scal = led.commit(1e-3)
    assert scal["arrived"] == [1, 1]
    assert scal["l_plus"] == np.float32(np.float32(4.0 + 3.5) / 2)


def test_ledger_rejects_stale_epoch_step_and_foreign_run():
    from repro.swarm.coordinator import StepLedger
    led = StepLedger("r", 5, 99, 3, 2)
    mk = lambda **kw: proto.StepContribution(**{
        "run_id": "r", "membership_epoch": 3, "step": 5, "seed": 99,
        "shard_losses": {"0": [1.0, 2.0]}, **kw})
    assert led.add(mk(membership_epoch=2), 3) == "stale_epoch"
    assert led.add(mk(step=4), 3) == "stale_step"
    assert led.add(mk(run_id="other"), 3) == "run_id"
    assert led.add(mk(shard_losses={"7": [1.0, 2.0]}), 3) == "bad_shard"
    assert led.n_arrived == 0 and sum(led.rejected.values()) == 4
    assert led.add(mk(), 3) == "ok"
    assert led.missing() == [1]


def test_quorum_reweighting_matches_in_trainer_math():
    """Arrived-weighted mean == the trainer quorum_loss formula
    sum(w*l)/sum(w) over the arrived subset."""
    rng_np = np.random.default_rng(0)
    losses = rng_np.uniform(2, 6, size=8).astype(np.float32)
    pairs = [None if i in (2, 5) else (float(losses[i]), float(losses[i]))
             for i in range(8)]
    lp, _, arrived = commit.reduce_losses(pairs)
    arrived_mask = np.asarray(arrived, np.float32)
    want = np.sum(losses * arrived_mask) / np.sum(arrived_mask)
    np.testing.assert_allclose(float(lp), float(want), rtol=1e-6)


def test_commit_refuses_zero_arrived():
    with pytest.raises(ValueError):
        commit.reduce_losses([None, None])


# ================================================================ chaos
def test_chaos_schedule_is_deterministic():
    cfg = chaos_mod.ChaosConfig(seed=7, drop=0.5, delay_ms=3.0,
                                crashes=((1, 4),), partitions=((0, 2, 5),))
    a = chaos_mod.Chaos(cfg, worker_id=1)
    b = chaos_mod.Chaos(cfg, worker_id=1)
    decisions = [(k, t, at) for k in ("contribution", "commit")
                 for t in range(10) for at in range(3)]
    assert ([a.drop(*d) for d in decisions]
            == [b.drop(*d) for d in decisions])
    # a fresh attempt re-rolls the dice: not every attempt is dropped
    dropped = [a.drop("contribution", 3, at) for at in range(16)]
    assert not all(dropped) and any(dropped)
    # different workers get different streams
    c = chaos_mod.Chaos(cfg, worker_id=2)
    assert any(a.drop("contribution", t) != c.drop("contribution", t)
               for t in range(32))


def test_chaos_partition_windows_and_crash_points():
    cfg = chaos_mod.ChaosConfig(seed=0, drop=0.0, delay_ms=0.0,
                                crashes=((1, 4),), partitions=((0, 2, 5),))
    w0 = chaos_mod.Chaos(cfg, worker_id=0)
    w1 = chaos_mod.Chaos(cfg, worker_id=1)
    assert [w0.partitioned(t) for t in range(7)] == [
        False, False, True, True, True, True, False]
    assert not any(w1.partitioned(t) for t in range(7))
    # partition implies both directions drop
    assert w0.drop("contribution", 3) and w0.drop("commit", 3)
    assert w1.crash_point(4) and not w0.crash_point(4)
    assert not w1.crash_point(3)


def test_chaos_parsers_reject_malformed_schedules():
    assert chaos_mod.parse_crashes("1:4,0:9") == ((1, 4), (0, 9))
    assert chaos_mod.parse_partitions("1:3-5") == ((1, 3, 5),)
    for bad in ("1", "1:", "a:4", "1:4:9"):
        with pytest.raises(ValueError):
            chaos_mod.parse_crashes(bad)
    for bad in ("1:3", "1:5-3", "x:1-2"):
        with pytest.raises(ValueError):
            chaos_mod.parse_partitions(bad)


# ============================================================ spec layer
def test_validate_swarm_constraints():
    base = api.preset("swarm-smoke")
    api.validate(base)
    api.validate(api.with_overrides(base, {"swarm.workers": 4}))
    bad = [
        {"swarm.quorum": 1.5},
        {"swarm.quorum": 0.0},
        {"run.batch_size": 5},              # 5 % 2 != 0
        {"optimizer.mode": "fo"},
        {"estimator.name": "one_sided"},
        {"runtime.n_loss_shards": 4},
        {"swarm.chaos_crash": "nope"},
        {"swarm.chaos_partition": "1:9-3"},
        {"swarm.chaos_drop": 1.0},
    ]
    for ov in bad:
        with pytest.raises(api.SpecError):
            api.validate(api.with_overrides(base, ov))
    # workers may not exceed a pinned shard count
    with pytest.raises(api.SpecError):
        api.validate(api.with_overrides(base, {"swarm.n_shards": 2,
                                               "swarm.workers": 4}))


def test_swarm_shards_derivation():
    import importlib
    vmod = importlib.import_module("repro.api.validate")
    base = api.preset("swarm-smoke")
    assert vmod.swarm_active(base)
    assert not vmod.swarm_active(api.preset("tiny-smoke"))
    assert vmod.swarm_shards(base) == 2
    assert vmod.swarm_shards(
        api.with_overrides(base, {"swarm.n_shards": 4})) == 4


# ======================================================= one chip per worker
def test_swarm_refuses_more_workers_than_tpu_chips(monkeypatch):
    """On a TPU host each worker needs a chip of its own: asking for
    more workers than chips fails before any process or socket starts."""
    from repro.swarm import coordinator, driver
    monkeypatch.setattr(driver, "tpu_chips", lambda: 4)

    def no_coordinator(*a, **k):
        raise AssertionError("coordinator built despite too many workers")

    monkeypatch.setattr(coordinator, "Coordinator", no_coordinator)
    spec = api.with_overrides(api.preset("swarm-smoke"),
                              {"swarm.workers": 5})
    with pytest.raises(ValueError, match="4 chips"):
        driver.run_swarm(spec)


def test_swarm_worker_env_gives_each_slot_one_chip():
    from repro.swarm import driver
    envs = [driver._worker_env(slot, chips=4) for slot in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    # off a TPU host the workers inherit the parent's platform untouched
    assert "TPU_VISIBLE_CHIPS" not in driver._worker_env(0, chips=0)


def test_tpu_chips_zero_when_jax_platforms_excludes_tpu(monkeypatch):
    from repro.swarm import driver
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert driver.tpu_chips() == 0


# ==================================================== subprocess e2e (slow)
def _rows(runs_root):
    (run_dir,) = [d for d in pathlib.Path(runs_root).iterdir() if d.is_dir()]
    with open(run_dir / "steps.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return run_dir, rows


def _stream(rows):
    return [[r.get(k) for k in STREAM_KEYS] for r in rows]


def _smoke_spec(tmp, **over):
    spec = api.with_overrides(api.preset("swarm-smoke"), {
        "run.steps": 10, "run.ckpt_every": 5,
        "run.ckpt_dir": str(tmp / "ckpt"), **over})
    return dataclasses.replace(
        spec, telemetry=dataclasses.replace(spec.telemetry,
                                            runs_dir=str(tmp / "runs")))


@pytest.mark.slow
def test_two_worker_swarm_bit_identical_to_single_process(tmp_path):
    """Acceptance gate: swarm(2 workers) == single-process trainer on the
    same spec — scalar stream and final parameters, to the bit."""
    jax = pytest.importorskip("jax")
    from repro.checkpoint.manager import CheckpointManager
    from repro.swarm import driver

    sw = _smoke_spec(tmp_path / "sw")
    summary = driver.run_swarm(sw, runs_root=str(tmp_path / "sw" / "runs"))
    _, rows_sw = _rows(tmp_path / "sw" / "runs")
    # every worker reported the device it built on
    assert sorted(summary["worker_devices"]) == ["0", "1"]
    assert {d["platform"] for d in summary["worker_devices"].values()} \
        == {jax.default_backend()}
    # the CPU backend opens no accelerator device node
    assert all(d["device_files"] == []
               for d in summary["worker_devices"].values())

    sp = _smoke_spec(tmp_path / "sp")
    hist = api.run(sp)["history"]
    _, rows_sp = _rows(tmp_path / "sp" / "runs")

    assert _stream(rows_sw) == _stream(rows_sp)

    # the swarm's designated-worker checkpoint holds the same bits the
    # single-process trainer finished with
    ck = CheckpointManager(str(tmp_path / "sw" / "ckpt"))
    params, step, _, _ = ck.restore(hist["final_params"])
    assert step == sw.run.steps
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(hist["final_params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_crash_rejoin_and_replay(tmp_path):
    """One injected hard crash: the epoch bumps, shards reassign, the
    respawned worker rejoins elastically, and not one committed bit
    differs from a calm run.  The recorded run passes launch replay."""
    pytest.importorskip("jax")
    from repro.launch import replay
    from repro.swarm import driver

    calm = _smoke_spec(tmp_path / "calm", **{"run.steps": 30,
                                             "run.ckpt_every": 10})
    driver.run_swarm(calm, runs_root=str(tmp_path / "calm" / "runs"))
    _, rows_calm = _rows(tmp_path / "calm" / "runs")

    chaos = _smoke_spec(tmp_path / "chaos", **{
        "run.steps": 30, "run.ckpt_every": 10,
        "swarm.chaos_crash": "1:3", "swarm.chaos_seed": 7})
    summary = driver.run_swarm(chaos,
                               runs_root=str(tmp_path / "chaos" / "runs"))
    run_dir, rows_chaos = _rows(tmp_path / "chaos" / "runs")

    assert chaos_mod.CRASH_EXIT in summary["worker_exits"]
    assert summary["membership_epochs"] >= 3    # 2 joins + death (+ rejoin)
    assert _stream(rows_chaos) == _stream(rows_calm)
    out = replay.replay_run(str(run_dir))
    assert out["ok"], out


@pytest.mark.slow
def test_quorum_degraded_run_replays(tmp_path):
    """A partitioned worker forces deadline commits from a partial shard
    set; the recorded ``arrived`` mask makes the run replayable anyway."""
    pytest.importorskip("jax")
    from repro.launch import replay
    from repro.swarm import driver

    spec = _smoke_spec(tmp_path, **{
        "swarm.n_shards": 4, "swarm.quorum": 0.5,
        "swarm.step_deadline_s": 1.0,
        "swarm.chaos_seed": 7, "swarm.chaos_partition": "1:2-6"})
    driver.run_swarm(spec, runs_root=str(tmp_path / "runs"))
    run_dir, rows = _rows(tmp_path / "runs")
    degraded = [r for r in rows if 0 in (r.get("arrived") or [])]
    assert degraded, "partition produced no quorum-degraded step"
    for r in degraded:
        assert len(r["shard_losses"]) == sum(r["arrived"])
    out = replay.replay_run(str(run_dir))
    assert out["ok"], out
