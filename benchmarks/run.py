"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs longer budgets.

``--json [PATH]`` (default ``BENCH_energy.json``) instead records the
energy trajectory: a short measured E²-Train run on the paper's ResNet
through ``Trainer.energy_report()``, plus the config-derived Table 3 sweep
for ResNet-74 — every field straight from :class:`EnergyReport`, so CI can
diff the numbers PR over PR.

``--json-conv [PATH]`` (default ``BENCH_conv.json``) records the
fused-conv trajectory: implicit-GEMM vs materialized-im2col activation
bytes moved per training step on the paper-shaped ResNet-74 config plus
per-shape rows and a CPU proxy steps/s A/B (benchmarks/bench_conv.py).
Both traffic directions (fwd/bwd x-side AND the dx side) are counted per
path; exits nonzero if any path's byte accounting is incomplete.

``--json-attn [PATH]`` (default ``BENCH_attn.json``) records the
flash-attention trajectory: PSG flash backward vs materialized (S, T)
path attention bytes moved per training step on the paper-shaped LM
config, per-shape rows and a CPU proxy LM A/B with the measured
attention-backward fallback ratio (benchmarks/bench_attn.py).  Both
traffic directions are counted per path; exits nonzero if any path's
byte accounting is incomplete.

``--json-audit [PATH]`` (default ``BENCH_audit.json``) records the static
cost audit: per-layer CostModel vs jaxpr vs compiled-HLO reconciliation
for the paper backbones and the smoke LM, plus the full lint battery —
Pallas kernel linter, repo convention linter, precision-flow lint and
hot-loop lint (benchmarks/bench_audit.py).  Exits 1 when the audit or a
linter *fails*, 2 when a lint pass *errors* (a crashing linter must not
pass CI silently) — this is the CI gate.

``--json-ft [PATH]`` (default ``BENCH_ft.json``) records the
fault-injection recovery battery (benchmarks/bench_ft.py): corruption
detection + fallback per injected mode, producer-raise propagation,
write-failure retry/surfacing, and the supervised kill-and-restart smoke
with its bitwise-vs-uninterrupted verdict.  Exits 1 when any recovery
failed — the fault-injection CI gate.  CI uploads all BENCH JSONs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# invoked as `python benchmarks/run.py`: sys.path[0] is benchmarks/, so put
# the repo root there too for the `from benchmarks import ...` bench imports
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def energy_json(fast: bool = True) -> dict:
    """EnergyReport fields for the trajectory record (see module doc)."""
    import jax

    from repro.configs.paper_cnns import cnn_model, resnet74
    from repro.core.config import (E2TrainConfig, Experiment, PSGConfig,
                                   SLUConfig, SMDConfig, TrainConfig)
    from repro.core.ledger import EnergyLedger
    from repro.data.synthetic import GaussianImageTask, make_image_batch
    from repro.training.train_step import init_train_state
    from repro.training.trainer import Trainer

    # config-derived Table 3 sweep: ResNet-74 at the paper's three operating
    # points, no training required — measured columns are null (≠ 0)
    table3 = []
    for skip in (0.2, 0.4, 0.6):
        op = E2TrainConfig(smd=SMDConfig(enabled=True, drop_prob=0.5),
                           slu=SLUConfig(enabled=True, target_skip=skip),
                           psg=PSGConfig(enabled=True))
        table3.append(EnergyLedger(resnet74(e2=op))
                      .report(validate_against_hlo=True).to_dict())

    # measured: a short full-E²-Train CNN run through the shared Trainer
    depth, steps = (14, 12) if fast else (26, 40)
    e2 = E2TrainConfig(smd=SMDConfig(enabled=True, drop_prob=0.5),
                       slu=SLUConfig(enabled=True, alpha=5e-3,
                                     target_skip=0.2),
                       psg=PSGConfig(enabled=True, swa=False))
    exp = Experiment(model=cnn_model(f"resnet{depth}", depth), e2=e2,
                     train=TrainConfig(global_batch=8, lr=0.03,
                                       optimizer="psg", total_steps=steps,
                                       schedule="constant"),
                     task="cifar_cnn")
    task = GaussianImageTask(num_classes=10, snr=2.0)
    tr = Trainer(exp, init_train_state(jax.random.PRNGKey(0), exp),
                 lambda s, sh: make_image_batch(task, 0, s, sh, 8))
    tr.run(steps)
    return {"table3_config_derived": table3,
            "measured_run": tr.energy_report(
                steps=steps, validate_against_hlo=True).to_dict()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (smd,slu,psg,e2train,"
                         "cnn,convergence,kernels,conv,attn,"
                         "roofline,audit,ft)")
    ap.add_argument("--json", nargs="?", const="BENCH_energy.json",
                    default=None, metavar="PATH",
                    help="write the EnergyReport trajectory record to PATH "
                         "and exit (skips the CSV benches)")
    ap.add_argument("--json-conv", nargs="?", const="BENCH_conv.json",
                    default=None, metavar="PATH",
                    help="write the fused-conv record (implicit-GEMM vs "
                         "im2col: activation bytes moved + CPU proxy "
                         "steps/s) to PATH and exit (skips the CSV benches)")
    ap.add_argument("--json-attn", nargs="?", const="BENCH_attn.json",
                    default=None, metavar="PATH",
                    help="write the flash-attention record (PSG flash "
                         "backward vs materialized path: attention bytes "
                         "moved + CPU proxy steps/s + measured fallback) to "
                         "PATH and exit (skips the CSV benches)")
    ap.add_argument("--json-audit", nargs="?", const="BENCH_audit.json",
                    default=None, metavar="PATH",
                    help="write the static cost-audit record (CostModel vs "
                         "jaxpr vs HLO + kernel/repo lint) to PATH and exit "
                         "nonzero on divergence or lint findings")
    ap.add_argument("--json-ft", nargs="?", const="BENCH_ft.json",
                    default=None, metavar="PATH",
                    help="write the fault-injection recovery record "
                         "(corruption fallback, producer-raise, write "
                         "retry/surfacing, kill-and-restart) to PATH and "
                         "exit nonzero if any recovery failed")
    args = ap.parse_args(argv)
    fast = not args.full

    if args.json or args.json_conv \
            or args.json_attn or args.json_audit \
            or args.json_ft:                                 # write all given
        if args.json:
            with open(args.json, "w") as f:
                json.dump(energy_json(fast=fast), f, indent=2)
            print(f"wrote {args.json}", file=sys.stderr)
        if args.json_conv:
            from benchmarks.bench_conv import (IncompleteAccountingError,
                                               conv_json)
            try:
                record = conv_json(fast=fast)
            except IncompleteAccountingError as e:
                print(f"conv byte accounting incomplete: {e}",
                      file=sys.stderr)
                sys.exit(1)
            with open(args.json_conv, "w") as f:
                json.dump(record, f, indent=2)
            print(f"wrote {args.json_conv}", file=sys.stderr)
        if args.json_attn:
            from benchmarks.bench_attn import (IncompleteAccountingError,
                                               attn_json)
            try:
                record = attn_json(fast=fast)
            except IncompleteAccountingError as e:
                print(f"attention byte accounting incomplete: {e}",
                      file=sys.stderr)
                sys.exit(1)
            with open(args.json_attn, "w") as f:
                json.dump(record, f, indent=2)
            print(f"wrote {args.json_attn}", file=sys.stderr)
        if args.json_audit:
            from benchmarks.bench_audit import audit_json
            record = audit_json(fast=fast)
            with open(args.json_audit, "w") as f:
                json.dump(record, f, indent=2)
            print(f"wrote {args.json_audit}", file=sys.stderr)
            # a linter that CRASHED is not a linter that passed: distinct
            # exit code so CI can tell "findings" (1) from "broken
            # tooling" (2) — a crashing lint pass must never gate green
            if record.get("lint_errors"):
                print(f"lint pass(es) errored: "
                      f"{', '.join(record['lint_errors'])}", file=sys.stderr)
                sys.exit(2)
            if not record["all_passed"]:
                sys.exit(1)
        if args.json_ft:
            from benchmarks.bench_ft import ft_json
            record = ft_json(fast=fast)
            with open(args.json_ft, "w") as f:
                json.dump(record, f, indent=2)
            print(f"wrote {args.json_ft}", file=sys.stderr)
            if not record["all_recovered"]:
                failed = [s["scenario"] for s in record["scenarios"]
                          if not s["recovered"]]
                print(f"recovery failed: {', '.join(failed)}",
                      file=sys.stderr)
                sys.exit(1)
        return

    from benchmarks import (bench_attn, bench_audit, bench_cnn, bench_conv,
                            bench_convergence, bench_e2train, bench_ft,
                            bench_kernels, bench_psg, bench_slu, bench_smd,
                            roofline)

    benches = {
        "smd": bench_smd.run,           # Fig. 3a/3b, Tab. 1
        "slu": bench_slu.run,           # Fig. 4
        "psg": bench_psg.run,           # Tab. 2
        "e2train": bench_e2train.run,   # Tab. 3
        "cnn": bench_cnn.run,           # Tab. 4 (paper backbones)
        "convergence": bench_convergence.run,  # Fig. 5
        "kernels": bench_kernels.run,
        "conv": bench_conv.run,         # §Kernels (implicit-GEMM vs im2col)
        "attn": bench_attn.run,         # §Kernels (PSG flash bwd vs (S,T))
        "roofline": roofline.run,       # §Roofline (from dry-run artifact)
        "audit": bench_audit.run,       # §Analysis (static cost audit)
        "ft": bench_ft.run,             # §Fault-tolerance (injected faults)
    }
    only = set(args.only.split(",")) if args.only else set(benches)

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn(fast=fast):
                print(row, flush=True)
        except Exception as e:  # noqa
            # the other benches still run, but the exit code says one broke
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"bench(es) raised: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
