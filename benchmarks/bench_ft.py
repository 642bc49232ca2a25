"""Fault-injection recovery record (DESIGN.md §Fault-tolerance).

Every scenario injects a REAL fault through ``repro.ft.faults`` and records
whether the recovery machinery did its job:

* ``corrupt_recovery`` — one scenario per corruption mode
  (truncate/flip/tamper/partial): integrity verification must detect the
  damage and restore must fall back to the previous intact step;
* ``producer_raise`` — a raising data producer must propagate to the
  consumer within one step (the pre-PR 10 silent-hang bug);
* ``failing_writer`` — transient write failures are absorbed by
  retry-with-backoff; terminal failures surface as CheckpointWriteError
  (never a silently dead daemon thread);
* ``kill_restart`` — the end-to-end tentpole: a launcher worker
  hard-killed mid-run, supervised kill-and-restart onto a smaller world,
  resume from the last intact checkpoint, final checkpoint BIT-IDENTICAL
  to an uninterrupted run (counter-based schedule consistency).

``ft_json`` returns the record; ``run.py --json-ft`` writes BENCH_ft.json
and exits nonzero when any recovery failed — this is the CI gate.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_state():
    # numpy, not jnp: this process must stay off the device, because the
    # kill-and-restart scenario's launcher children need the chip
    return {"params": {"w": np.arange(12.0, dtype=np.float32).reshape(3, 4),
                       "b": np.ones((4,), np.float32)},
            "step": np.int32(5)}


def _host_scenarios():
    """Every scenario that runs in this process; none of them touches a
    JAX device."""
    return (_corrupt_recovery_scenarios() + [_producer_raise_scenario()]
            + _failing_writer_scenarios())


def _corrupt_recovery_scenarios():
    from repro.ft import faults
    from repro.ft.checkpoint import (latest_intact_step, restore_checkpoint,
                                     save_checkpoint, verify_checkpoint)
    rows = []
    for mode in faults.CORRUPT_MODES:
        with tempfile.TemporaryDirectory() as d:
            st = _tiny_state()
            save_checkpoint(d, st, 3)
            save_checkpoint(d, st, 7)
            faults.corrupt_checkpoint(d, 7, mode)
            detected, reason = verify_checkpoint(d, 7)
            detected = not detected
            fell_back = latest_intact_step(d) == 3
            try:
                _, got = restore_checkpoint(d, st)
                restored_ok = got == 3
            except Exception as e:  # noqa: BLE001 - recorded, not swallowed
                restored_ok, reason = False, repr(e)
            rows.append({"scenario": f"corrupt_{mode}",
                         "detected": detected, "fell_back": fell_back,
                         "reason": reason,
                         "recovered": detected and fell_back and restored_ok})
    return rows


def _producer_raise_scenario():
    from repro.data.pipeline import DataPipeline
    from repro.ft.faults import raising_at_step
    mk = raising_at_step(lambda s, sh: {"x": np.full((2,), s)}, 3)
    pipe = DataPipeline(mk, None, prefetch=2)
    got, err, t0 = [], None, time.perf_counter()
    try:
        for _ in range(10):
            got.append(next(pipe)[0])
    except RuntimeError as e:
        err = e
    surfaced_s = time.perf_counter() - t0
    pipe.close()
    recovered = (err is not None and got == [0, 1, 2] and surfaced_s < 5.0)
    return {"scenario": "producer_raise", "good_steps_consumed": got,
            "surfaced_s": round(surfaced_s, 3), "recovered": recovered}


def _failing_writer_scenarios():
    from repro.ft import faults
    from repro.ft.checkpoint import (WRITE_RETRIES, CheckpointWriteError,
                                     save_checkpoint, verify_checkpoint,
                                     wait_for_saves)
    rows = []
    with tempfile.TemporaryDirectory() as d:
        with faults.failing_writer(fails=WRITE_RETRIES - 1) as count:
            save_checkpoint(d, _tiny_state(), 1)
        intact = verify_checkpoint(d, 1)[0]
        rows.append({"scenario": "writer_transient_retry",
                     "injected_failures": count["n"],
                     "recovered": intact and count["n"] == WRITE_RETRIES - 1})
    with tempfile.TemporaryDirectory() as d:
        surfaced = False
        with faults.failing_writer():            # never recovers
            save_checkpoint(d, _tiny_state(), 1, async_save=True)
            try:
                wait_for_saves()
            except CheckpointWriteError:
                surfaced = True
        rows.append({"scenario": "writer_terminal_surfaced",
                     "recovered": surfaced and wait_for_saves() == {}})
    return rows


def _launcher(*args):
    return [sys.executable, "-m", "repro.launch.train",
            "--arch", "llama3_8b", "--smoke", "--log-every", "0", *args]


def _kill_restart_scenario(fast: bool = True):
    from repro.ft.checkpoint import latest_intact_step
    from repro.ft.faults import KILL_EXIT_CODE
    from repro.ft.supervisor import Supervisor
    steps = 8 if fast else 16
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as d:
        ckpt, scratch, ref = (os.path.join(d, n)
                              for n in ("ckpt", "scratch", "ref"))

        def make_cmd(world, rank, resume):
            args = ["--steps", str(steps), "--e2train", "smd",
                    "--ckpt-every", "1",
                    "--ckpt", ckpt if rank == 0 else scratch]
            if resume is not None:
                args += ["--resume"]
            elif world > 1 and rank == world - 1:
                args += ["--ft-kill-at-step", str(steps // 2 + 1)]
            return _launcher(*args)

        sup = Supervisor(make_cmd, world=2, ckpt_dir=ckpt, env=env)
        try:
            sup.run()
            supervised_ok = True
        except Exception as e:  # noqa: BLE001 - recorded, not swallowed
            supervised_ok, err = False, repr(e)
        att = sup.summary()
        final_intact = latest_intact_step(ckpt)

        ref_run = subprocess.run(
            _launcher("--steps", str(steps), "--e2train", "smd",
                      "--ckpt-every", "1", "--ckpt", ref),
            cwd=REPO, env=env, capture_output=True, text=True, timeout=580)

        bitwise = False
        if supervised_ok and ref_run.returncode == 0 \
                and final_intact == steps - 1:
            a = np.load(os.path.join(ckpt, f"step_{steps - 1:08d}.npz"))
            b = np.load(os.path.join(ref, f"step_{steps - 1:08d}.npz"))
            bitwise = set(a.files) == set(b.files) and all(
                np.array_equal(a[k], b[k]) for k in a.files)
        row = {"scenario": "kill_restart", "steps": steps,
               "kill_exit_code": KILL_EXIT_CODE, "attempts": att["attempts"],
               "restarts": att["restarts"], "final_intact_step": final_intact,
               "bitwise_match_vs_uninterrupted": bitwise,
               "recovered": supervised_ok and bitwise}
        if not supervised_ok:
            row["error"] = err
        return row


def ft_json(fast: bool = True) -> dict:
    """The fault-injection recovery record (see module doc)."""
    scenarios = _host_scenarios()
    scenarios.append(_kill_restart_scenario(fast=fast))
    return {"scenarios": scenarios,
            "all_recovered": all(s["recovered"] for s in scenarios)}


def run(fast: bool = True):
    """CSV rows for the bench driver."""
    record = ft_json(fast=fast)
    for s in record["scenarios"]:
        yield f"ft_{s['scenario']},0.0,recovered={s['recovered']}"
    yield f"ft_all,0.0,all_recovered={record['all_recovered']}"
