"""Fault-injection harness (ft/faults.py) + elastic supervisor (ft/supervisor).

Every recovery path gets a REAL injected fault:

* supervisor policy loop against deterministic worker exit codes;
* kill-and-restart through ``launch/train.py``: a worker hard-killed
  mid-run (``--ft-kill-at-step``) is detected, the world shrinks, and the
  resumed run's final checkpoint is BIT-IDENTICAL to an uninterrupted
  run's — the counter-based data/SMD schedule makes the restarted step
  stream consistent by construction;
* elastic mesh shrink: killed on a 2-device data-parallel mesh, resumed
  on a 1-device mesh from the last *intact* checkpoint (a save torn by
  the kill fails checksum verification and is skipped);
* a real ``jax.distributed`` 2-process world: rank/world discovery, per-
  process data shards and per-process checkpoint streams (CPU backend has
  no cross-process collectives, so each rank trains its own shard — the
  coordinator plumbing and counter-based sharding are what this smoke
  pins).

Subprocess tests are ``slow`` (excluded from tier-1); CI runs them in the
dedicated fault-injection job.
"""
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from repro.ft import faults
from repro.ft.checkpoint import intact_steps, latest_intact_step
from repro.ft.supervisor import (RestartPolicy, Supervisor, SupervisorError,
                                 free_tcp_port)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _launcher(*args):
    return [sys.executable, "-m", "repro.launch.train",
            "--arch", "llama3_8b", "--smoke", "--log-every", "0", *args]


# ---------------------------------------------------------------------------
# injector units
# ---------------------------------------------------------------------------


def test_raising_at_step_fires_deterministically():
    mk = faults.raising_at_step(lambda s, sh: {"s": s}, 5)
    assert mk(4, 0) == {"s": 4}
    with pytest.raises(RuntimeError, match="step 5"):
        mk(5, 0)
    with pytest.raises(RuntimeError):
        mk(9, 0)                       # >= step: a drop cannot skip the fault


def test_slow_at_step_delays_only_listed_steps():
    mk = faults.slow_at_step(lambda s, sh: {"s": s}, [2], 0.2)
    t0 = time.perf_counter()
    mk(1, 0)
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    mk(2, 0)
    slow = time.perf_counter() - t0
    assert slow >= 0.2 > fast


def test_corrupt_checkpoint_rejects_unknown_mode():
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="unknown corruption mode"):
            faults.corrupt_checkpoint(d, 0, "gamma-ray")


# ---------------------------------------------------------------------------
# supervisor policy loop (workers = trivial subprocesses, no JAX)
# ---------------------------------------------------------------------------


def _exit_cmd(code):
    return [sys.executable, "-c", f"import sys; sys.exit({code})"]


def test_supervisor_clean_world_single_attempt():
    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(lambda w, r, resume: _exit_cmd(0), world=2,
                         ckpt_dir=d)
        attempts = sup.run()
    assert len(attempts) == 1
    assert attempts[0].outcome == "ok"
    assert attempts[0].exit_codes == [0, 0]
    assert sup.summary()["restarts"] == 0


def test_supervisor_shrinks_world_and_recovers():
    """One worker dies (injected exit code) -> the attempt is torn down,
    the world shrinks by the death count, and the smaller world succeeds."""
    def make_cmd(world, rank, resume):
        # rank 1 of the 2-world dies with the injected-kill code; the
        # re-formed 1-world runs clean
        code = faults.KILL_EXIT_CODE if (world == 2 and rank == 1) else 0
        return _exit_cmd(code)

    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(make_cmd, world=2, ckpt_dir=d)
        attempts = sup.run()
    assert [a.world for a in attempts] == [2, 1]
    assert attempts[0].outcome == "worker-died"
    assert faults.KILL_EXIT_CODE in attempts[0].exit_codes
    assert attempts[1].outcome == "ok"
    assert attempts[1].resume_step is None       # no checkpoint ever landed


def test_supervisor_gives_up_after_max_restarts():
    # exactly one rank dies per attempt, so the world shrinks by one each
    # time and the RESTART budget (not the world floor) is what trips
    def make_cmd(world, rank, resume):
        return _exit_cmd(5 if rank == world - 1 else 0)

    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(make_cmd, world=3, ckpt_dir=d,
                         policy=RestartPolicy(max_restarts=1))
        with pytest.raises(SupervisorError, match="gave up"):
            sup.run()
    assert [a.world for a in sup.attempts] == [3, 2]
    assert sup.attempts[-1].outcome == "aborted"


def test_supervisor_respects_min_world():
    with tempfile.TemporaryDirectory() as d:
        sup = Supervisor(lambda w, r, resume: _exit_cmd(5), world=2,
                         ckpt_dir=d,
                         policy=RestartPolicy(max_restarts=5, min_world=2))
        with pytest.raises(SupervisorError, match="min_world"):
            sup.run()
    assert len(sup.attempts) == 1                # never relaunched below floor


# ---------------------------------------------------------------------------
# kill-and-restart through the real launcher (slow: subprocess training)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kill_and_restart_resumes_bit_consistent():
    """THE tentpole acceptance test: a worker hard-killed mid-run is
    detected by the supervisor, the world shrinks 2 -> 1, the relaunched
    worker resumes from the last INTACT checkpoint — and the final
    checkpoint is bit-identical to an uninterrupted run, step counter and
    SMD drop stream included (counter-based schedule => the restart
    replays the exact decision stream)."""
    steps = 10
    with tempfile.TemporaryDirectory() as d:
        ckpt, scratch, ref = (os.path.join(d, n)
                              for n in ("ckpt", "scratch", "ref"))

        def make_cmd(world, rank, resume):
            args = ["--steps", str(steps), "--e2train", "smd",
                    "--ckpt-every", "1"]
            # rank 0 owns the supervised checkpoint stream; other ranks
            # write elsewhere (single-process workers are all shard 0)
            args += ["--ckpt", ckpt if rank == 0 else scratch]
            if resume is not None:
                args += ["--resume"]
            elif world > 1 and rank == world - 1:
                # first attempt only: the last rank is hard-killed mid-run
                args += ["--ft-kill-at-step", "6"]
            return _launcher(*args)

        sup = Supervisor(make_cmd, world=2, ckpt_dir=ckpt, env=_env())
        attempts = sup.run()

        assert [a.world for a in attempts] == [2, 1]
        assert attempts[0].outcome == "worker-died"
        assert faults.KILL_EXIT_CODE in attempts[0].exit_codes
        assert attempts[1].outcome == "ok"
        # the restart resumed from an intact checkpoint, not from scratch
        # and not from a torn save
        assert attempts[1].resume_step is not None
        assert attempts[1].resume_step < steps
        assert latest_intact_step(ckpt) == steps - 1

        # uninterrupted reference with the same counters
        out = subprocess.run(
            _launcher("--steps", str(steps), "--e2train", "smd",
                      "--ckpt-every", "1", "--ckpt", ref),
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=580)
        assert out.returncode == 0, out.stderr[-2000:]

        a = np.load(os.path.join(ckpt, f"step_{steps - 1:08d}.npz"))
        b = np.load(os.path.join(ref, f"step_{steps - 1:08d}.npz"))
        assert set(a.files) == set(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.slow
def test_elastic_mesh_shrink_kill_and_restart():
    """Killed on a 2-device data-parallel mesh mid-chunked-run; resumed on
    a 1-device mesh (launch/train.py restores the last intact checkpoint
    and reshard_state places it onto the smaller mesh) and runs the step
    budget to completion."""
    steps = 16
    with tempfile.TemporaryDirectory() as d:
        killed = subprocess.run(
            _launcher("--steps", str(steps), "--e2train", "smd",
                      "--ckpt", d, "--ckpt-every", "1", "--chunk-steps", "2",
                      "--devices", "2", "--mesh-data", "2",
                      "--ft-kill-at-step", "12"),
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=580)
        assert killed.returncode == faults.KILL_EXIT_CODE
        survivors = intact_steps(d)

        resumed = subprocess.run(
            _launcher("--steps", str(steps), "--e2train", "smd",
                      "--ckpt", d, "--ckpt-every", "1", "--chunk-steps", "2",
                      "--devices", "1", "--mesh-data", "1", "--resume"),
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=580)
        assert resumed.returncode == 0, resumed.stderr[-2000:]
        if survivors:                   # the kill usually leaves intact saves
            assert f"resumed from intact step {survivors[-1]}" \
                in resumed.stdout
            assert "'data': 1" in resumed.stdout     # resharded onto 1-dev
        assert latest_intact_step(d) == steps - 1


@pytest.mark.slow
def test_jax_distributed_two_process_world():
    """A real jax.distributed world of 2 processes on one host: coordinator
    handshake, rank/world discovery (process_shard), per-process data
    shards and per-process checkpoint streams all work end to end."""
    steps = 4
    with tempfile.TemporaryDirectory() as d:
        port = free_tcp_port()
        procs = [subprocess.Popen(
            _launcher("--steps", str(steps), "--ckpt", d, "--ckpt-every", "1",
                      "--distributed", "--coordinator", f"localhost:{port}",
                      "--num-processes", "2", "--process-id", str(i)),
            cwd=REPO, env=_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True) for i in range(2)]
        outs = [p.communicate(timeout=580) for p in procs]
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, se[-2000:]
        # per-process checkpoint streams, both complete and intact
        d0, d1 = (os.path.join(d, f"proc{i:03d}") for i in range(2))
        assert latest_intact_step(d0) == steps - 1
        assert latest_intact_step(d1) == steps - 1
        # counter-based sharding: the two ranks trained DIFFERENT shards,
        # so their params diverge (identical params would mean shard 0 ran
        # twice — the multi-host bug this smoke exists to catch)
        a = np.load(os.path.join(d0, f"step_{steps - 1:08d}.npz"))
        b = np.load(os.path.join(d1, f"step_{steps - 1:08d}.npz"))
        assert any(not np.array_equal(a[k], b[k]) for k in a.files)


def test_bench_ft_parent_stays_off_the_device():
    """bench_ft's in-process scenarios must not initialize a JAX backend:
    on a chip machine the kill-and-restart children it starts next could
    not get the chip from a parent that holds it."""
    code = ("import sys; sys.path.insert(0, '.')\n"
            "from benchmarks import bench_ft\n"
            "rows = bench_ft._host_scenarios()\n"
            "assert all(r['recovered'] for r in rows), rows\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                   check=True, timeout=120)
