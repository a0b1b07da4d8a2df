"""Swarm process driver: spawn, supervise, respawn (DESIGN.md §14).

``run_swarm(spec)`` runs the coordinator in-process and launches
``swarm.workers`` local worker processes (``python -m repro.launch
swarm --attach host:port``).  A supervisor thread watches them: a
worker that dies mid-run — injected ``chaos_crash`` or otherwise — is
respawned (unless ``respawn=False``), and the replacement demonstrates
the elastic-join path: it attaches with nothing but the address,
rebuilds from the wire-shipped spec, and folds the committed
``(seed, g)`` log forward to the live step.

``attach`` mode is the worker half: connect to an existing coordinator
and serve until the run completes.

One process per chip: a chip belongs to one process at a time, so the
parent pins its own JAX work (the coordinator's selection metrics) to
the host CPU before any backend starts, and on a TPU host each worker
slot is given one chip through libtpu's per-process variables.  The
chips are counted over PCI, which starts no backend; more workers than
chips is an error.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_MAX_RESPAWNS_PER_SLOT = 3


def _src_root() -> str:
    import repro
    # namespace package: __file__ is None, __path__ still points at src/repro
    pkg_dir = (os.path.dirname(repro.__file__) if repro.__file__
               else list(repro.__path__)[0])
    return os.path.dirname(os.path.abspath(pkg_dir))


def _worker_cmd(host: str, port: int) -> List[str]:
    return [sys.executable, "-m", "repro.launch", "swarm",
            "--attach", f"{host}:{port}"]


def tpu_chips() -> int:
    """TPU chips attached to this host, counted over PCI without
    starting a JAX backend; 0 where ``JAX_PLATFORMS`` keeps JAX off the
    TPU."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(slot: int, chips: int) -> Dict[str, str]:
    """The parent's environment plus, on a TPU host (``chips > 0``),
    chip ``slot`` alone: a one-chip process topology, with its own
    libtpu port so the workers' runtimes do not collide."""
    env = dict(os.environ)
    src = _src_root()
    prev = env.get("PYTHONPATH", "")
    if src not in prev.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + prev if prev else "")
    if chips:
        env.update(TPU_VISIBLE_CHIPS=str(slot),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_PORT=str(_free_port()))
    return env


def spawn_worker(host: str, port: int, slot: int = 0,
                 chips: int = 0) -> subprocess.Popen:
    return subprocess.Popen(_worker_cmd(host, port),
                            env=_worker_env(slot, chips))


def pin_parent_to_cpu():
    """Run this process's JAX on the host CPU so it never holds a chip
    a worker needs.  Raises if a non-CPU backend already started."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "the swarm parent already holds a "
                f"{jax.default_backend()} backend; its workers could not "
                "get the chips — start the swarm before any JAX work")
        return
    jax.config.update("jax_platforms", "cpu")


def run_swarm(spec, *, respawn: bool = True,
              runs_root: Optional[str] = None) -> Dict[str, Any]:
    """Coordinator + ``spec.swarm.workers`` supervised local workers.

    Returns the coordinator's summary dict (run_id, epochs, straggler
    steps, wire bytes/step, worker exit codes, worker devices).
    """
    from repro.swarm.coordinator import Coordinator

    if spec.swarm.workers < 1:
        raise ValueError("run_swarm needs swarm.workers >= 1 "
                         "(use --attach to join an existing swarm)")
    chips = tpu_chips()
    if chips and spec.swarm.workers > chips:
        raise ValueError(f"swarm.workers={spec.swarm.workers} but this TPU "
                         f"host has {chips} chips: one worker per chip")
    pin_parent_to_cpu()
    coord = Coordinator(spec, runs_root=runs_root)
    procs: List[Optional[subprocess.Popen]] = []
    respawns = [0] * spec.swarm.workers
    exits: List[int] = []
    done = threading.Event()

    def supervise():
        while not done.is_set():
            for slot, p in enumerate(procs):
                if p is None or p.poll() is None:
                    continue
                exits.append(p.returncode)
                procs[slot] = None
                if (respawn and not done.is_set()
                        and respawns[slot] < _MAX_RESPAWNS_PER_SLOT):
                    respawns[slot] += 1
                    procs[slot] = spawn_worker(coord.host, coord.port,
                                               slot, chips)
            time.sleep(0.1)

    sup = threading.Thread(target=supervise, daemon=True)
    try:
        for slot in range(spec.swarm.workers):
            procs.append(spawn_worker(coord.host, coord.port, slot, chips))
        sup.start()
        summary = coord.serve()
    finally:
        done.set()
        if sup.is_alive():
            sup.join(timeout=2.0)
        for p in procs:
            if p is None:
                continue
            try:
                p.wait(timeout=15.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5.0)
            exits.append(p.returncode)
    summary["worker_exits"] = exits
    summary["respawns"] = sum(respawns)
    return summary


def run_attached(address: str) -> Dict[str, Any]:
    """Worker half of ``launch swarm``: join the swarm at ``address``."""
    from repro.swarm import worker
    return worker.attach(address)
