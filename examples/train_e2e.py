"""End-to-end driver (deliverable b): train with E²-Train for a few hundred
steps, checkpointing + resume + SMD straggler policy, on either registered
task — the ~100M-param LM or the paper's CIFAR ResNet.

    PYTHONPATH=src python examples/train_e2e.py --steps 200
    PYTHONPATH=src python examples/train_e2e.py --steps 300 --resume
    PYTHONPATH=src python examples/train_e2e.py --task cifar_cnn --depth 14
    PYTHONPATH=src python examples/train_e2e.py --tiny --chunk-steps 8
    XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \
        python examples/train_e2e.py --tiny --chunk-steps 4 --mesh 2

By default uses a ~100M-parameter llama-style config; --tiny shrinks it for
fast CI runs.  Both tasks run the SAME Trainer/train_step stack — the task
registry (repro.tasks) supplies init/loss.  ``--chunk-steps K`` switches to
the compiled chunked loop (DESIGN.md §Loop: one lax.scan program per K
executed steps, prefetched data, chunk-boundary metric syncs); ``--mesh N``
adds N-way data-parallel execution and fails fast when fewer than N
devices are visible (on CPU, export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first — it must be
set before the JAX backend initializes, so the script can't do it for you).
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.paper_cnns import cnn_model
from repro.core.config import (E2TrainConfig, Experiment, ModelConfig,
                               PSGConfig, SLUConfig, SMDConfig, TrainConfig)
from repro.data.synthetic import (GaussianImageTask, MarkovLMTask,
                                  make_image_batch, make_lm_batch)
from repro.ft.checkpoint import latest_step, restore_checkpoint
from repro.launch.compile_cache import use_compile_cache
from repro.training.train_step import init_train_state
from repro.training.trainer import Trainer


def model_100m() -> ModelConfig:
    # ~109M params: 12L, d=768, 12H, kv 4, ff 2048, vocab 32k
    return ModelConfig(name="lm-100m", family="dense", num_layers=12,
                       d_model=768, num_heads=12, num_kv_heads=4,
                       d_ff=2048, vocab_size=32000)


def model_tiny() -> ModelConfig:
    return ModelConfig(name="lm-tiny", family="dense", num_layers=4,
                       d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                       vocab_size=512, dtype="float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["lm", "cifar_cnn"], default="lm")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (default: /tmp/e2train_ckpt_<task> "
                         "— per task, so --resume never crosses tasks)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--depth", type=int, default=74,
                    help="CIFAR ResNet depth (6n+2) for --task cifar_cnn")
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="compile K executed steps into one device program "
                         "(1 = per-step reference loop)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="N-way data-parallel mesh over the batch axis "
                         "(0 = single device)")
    ap.add_argument("--fused-conv", action="store_const", const=True,
                    default=None,
                    help="pin CNN convs to the fused implicit-GEMM kernels "
                         "(kernels/conv.py); unset, the kernel backend "
                         "decides (cifar_cnn task; DESIGN.md §Kernels)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-step straggler deadline: steps over it arm "
                         "SMD-style forced drops (0 = off; a step that "
                         "compiles can take far longer than a warm one)")
    args = ap.parse_args()
    use_compile_cache()
    if args.mesh > 1 and jax.device_count() < args.mesh:
        raise SystemExit(
            f"--mesh {args.mesh} needs {args.mesh} devices but only "
            f"{jax.device_count()} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.mesh} for the "
            "CPU demo")
    if args.ckpt is None:
        args.ckpt = f"/tmp/e2train_ckpt_{args.task}"

    e2 = E2TrainConfig(smd=SMDConfig(enabled=True, drop_prob=0.5),
                       slu=SLUConfig(enabled=True, alpha=1e-3),
                       psg=PSGConfig(enabled=True,
                                     fused_conv=args.fused_conv))
    tcfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                       lr=0.03, optimizer="psg", total_steps=args.steps,
                       schedule="step", microbatches=1)

    if args.task == "cifar_cnn":
        depth = 8 if args.tiny else args.depth     # --tiny shrinks both tasks
        model = cnn_model(f"resnet{depth}", depth,
                          width=8 if args.tiny else 16)
        exp = Experiment(model=model, e2=e2, train=tcfg, task="cifar_cnn")
        img_task = GaussianImageTask(num_classes=10, snr=2.0)
        bayes = "n/a"

        def make_batch(step, shard):
            return make_image_batch(img_task, 0, step, shard, args.batch)
        print(f"model {model.name} (CIFAR shapes, width {model.d_model})")
    else:
        model = model_tiny() if args.tiny else model_100m()
        exp = Experiment(model=model, e2=e2, train=tcfg)
        lm_task = MarkovLMTask(vocab=model.vocab_size)
        bayes = f"{lm_task.bayes_xent():.3f}"

        def make_batch(step, shard):
            return make_lm_batch(lm_task, 0, step, shard, args.batch, args.seq)
        print(f"model {model.name}: {model.param_count()/1e6:.1f}M params")

    state = init_train_state(jax.random.PRNGKey(0), exp)
    if args.resume and latest_step(args.ckpt) is not None:
        tree, step = restore_checkpoint(args.ckpt, state)
        state = jax.tree.map(jax.numpy.asarray, tree)
        print(f"resumed from checkpoint at step {step}")

    mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.mesh, 1), ("data", "model"))
        print(f"mesh: {args.mesh}-way data parallel over {mesh.devices.size} "
              "devices")
    trainer = Trainer(exp, state, make_batch, checkpoint_dir=args.ckpt,
                      checkpoint_every=50, deadline_s=args.deadline_s,
                      chunk_steps=args.chunk_steps, mesh=mesh)
    hist = trainer.run(args.steps, log_every=10)
    if hist:
        extras = ""
        fb = trainer.measured_psg_fallback()
        if fb is not None:
            extras = f"; measured PSG fallback {fb:.3f}"
        sps = trainer.steps_per_s()
        loop = (f"chunked K={args.chunk_steps}" if args.chunk_steps > 1
                or mesh is not None else "per-step")
        print(f"\nfinal loss {np.mean([h['loss'] for h in hist[-5:]]):.4f} "
              f"(bayes floor {bayes}); "
              f"executed {trainer.executed_steps}, "
              f"SMD-dropped {trainer.dropped_steps}{extras}; "
              f"checkpoints in {args.ckpt}")
        if sps:
            print(f"throughput: {sps:.2f} executed steps/s ({loop} loop)")
        # the run's energy accounting: this run's telemetry composed with
        # the per-layer cost model, measured next to assumed
        print("\n" + trainer.energy_report(steps=args.steps).summary())


if __name__ == "__main__":
    main()
