"""Training launcher.

Single-host demo / multi-host production entry point:

  PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --smoke \
      --steps 50 --e2train full --ckpt /tmp/ckpt

On a real cluster each host runs this with ``jax.distributed.initialize()``
(flag --distributed; pass --coordinator/--num-processes/--process-id
explicitly when the cluster env vars are absent, e.g. the test harness)
and the same counter-based data/SMD schedule; each process trains its own
data shard (``repro.distributed.process_shard``).  The checkpoint/elastic
machinery in ``repro.ft`` handles restarts, including onto a different
mesh shape (--mesh-data): resume picks the last *intact* checkpoint
(integrity-verified — a save torn by a crash is skipped) and
``ft/elastic.reshard_state`` places it onto the new mesh.  The
``ft/supervisor.Supervisor`` drives the kill-and-restart policy around
this entry point; ``--ft-kill-at-step`` is the matching fault hook
(DESIGN.md §Fault-tolerance).
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--e2train", default="off",
                    choices=["off", "full", "smd", "slu", "psg"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address (with "
                         "--distributed; defaults to cluster auto-detect)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="jax.distributed world size (with --coordinator)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank (with --coordinator)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host platform device count (testing)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="compile K executed steps into one device program "
                         "(DESIGN.md §Loop; 1 = per-step loop)")
    ap.add_argument("--mesh-data", type=int, default=0, metavar="N",
                    help="N-way data-parallel mesh over the batch axis "
                         "(0 = no mesh; 1 = single-device mesh, still "
                         "routes through sharding+reshard; combine with "
                         "--devices N)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-step straggler deadline: steps over it arm "
                         "SMD-style forced drops (0 = off)")
    ap.add_argument("--ft-kill-at-step", type=int, default=None,
                    metavar="STEP",
                    help="fault injection: hard-kill (os._exit) this "
                         "process when the data path reaches STEP "
                         "(ft/faults.kill_at_step; testing only)")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={args.devices}"

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.distributed:
        if args.coordinator is not None:
            jax.distributed.initialize(args.coordinator,
                                       num_processes=args.num_processes,
                                       process_id=args.process_id)
        else:
            jax.distributed.initialize()

    import dataclasses

    from repro.configs import get_experiment, smoke_experiment
    from repro.core.config import E2TrainConfig, PSGConfig, SLUConfig, SMDConfig
    from repro.data.synthetic import MarkovLMTask, make_lm_batch
    from repro.distributed import process_shard
    from repro.ft.checkpoint import latest_intact_step, restore_checkpoint
    from repro.training.train_step import init_train_state
    from repro.training.trainer import Trainer

    exp = smoke_experiment(args.arch) if args.smoke else get_experiment(args.arch)
    e2 = {
        "off": E2TrainConfig(),
        "full": E2TrainConfig.full(),
        "smd": E2TrainConfig(smd=SMDConfig(True)),
        "slu": E2TrainConfig(slu=SLUConfig(True)),
        "psg": E2TrainConfig(psg=PSGConfig(True)),
    }[args.e2train]
    tr_cfg = exp.train
    if args.e2train in ("full", "psg"):
        tr_cfg = dataclasses.replace(tr_cfg, optimizer="psg", lr=0.03)
    exp = exp.replace(e2=e2, train=tr_cfg)

    shard, num_shards = process_shard()
    ckpt_dir = args.ckpt
    if ckpt_dir and num_shards > 1:
        # each process owns its checkpoint stream: states are per-shard
        # on backends without cross-process collectives, and two ranks
        # racing one step file would tear the npz/manifest commit pair
        ckpt_dir = os.path.join(ckpt_dir, f"proc{shard:03d}")

    task = MarkovLMTask(vocab=exp.model.vocab_size)

    def make_batch(step, shard):
        b = make_lm_batch(task, exp.train.seed, step, shard,
                          exp.train.global_batch, exp.train.seq_len)
        if exp.model.frontend:
            key = jax.random.fold_in(jax.random.PRNGKey(7), step)
            b["frontend"] = jax.random.normal(
                key, (exp.train.global_batch, exp.model.frontend_tokens,
                      exp.model.d_model), exp.model.act_dtype)
        return b

    if args.ft_kill_at_step is not None:
        from repro.ft.faults import kill_at_step
        make_batch = kill_at_step(make_batch, args.ft_kill_at_step)

    mesh = None
    if args.mesh_data >= 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.mesh_data, 1), ("data", "model"))

    state = init_train_state(jax.random.PRNGKey(exp.train.seed), exp)
    start = 0
    if args.resume and ckpt_dir and latest_intact_step(ckpt_dir) is not None:
        # integrity-verified restore: falls back past truncated/corrupt/
        # partial saves to the newest intact step (ft/checkpoint)
        tree, step = restore_checkpoint(ckpt_dir, state)
        state = jax.tree.map(lambda a, b: jnp.asarray(b), state, tree)
        if mesh is not None:
            from repro.ft.elastic import reshard_state
            state = reshard_state(state, mesh)
        start = int(tree.step)    # restored counter = next nominal step
        print(f"resumed from intact step {step} (counter at {start})"
              + (f" on mesh {dict(mesh.shape)}" if mesh is not None else ""))

    trainer = Trainer(exp, state, make_batch, checkpoint_dir=ckpt_dir,
                      checkpoint_every=args.ckpt_every,
                      chunk_steps=args.chunk_steps, mesh=mesh,
                      deadline_s=args.deadline_s, shard=shard)
    # --steps is the TOTAL nominal step budget: a resumed run executes only
    # the remainder, so kill-and-restart reproduces an uninterrupted run's
    # counter stream exactly (the supervisor test's bit-consistency pin)
    hist = trainer.run(max(args.steps - start, 0), log_every=args.log_every)
    if hist:
        sps = trainer.steps_per_s()
        print(f"final loss: {hist[-1]['total_loss']:.4f} "
              f"(executed {trainer.executed_steps}, "
              f"SMD-dropped {trainer.dropped_steps}, "
              f"straggler-dropped {trainer.straggler_dropped_steps}, "
              f"{sps:.2f} steps/s)" if sps else
              f"final loss: {hist[-1]['total_loss']:.4f}")
    if trainer.save_errors:
        # a run whose final checkpoint did not land must not exit green:
        # the supervisor / CI would otherwise treat an unpersisted run as
        # a success and resume from a stale step
        print(f"final save FAILED: {sorted(trainer.save_errors)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
