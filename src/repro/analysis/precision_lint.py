"""Precision lint: the PR 7 bug class, caught statically (DESIGN.md
§Analysis).

PR 7 found by hand that the conv dx backward accumulated its k² tap sums
in ``gq.dtype`` — bf16 cotangents silently collapsed.  This pass makes
that class of bug a CI failure instead of a review catch: it runs the
:mod:`repro.analysis.dataflow` engine over every surface the repo ships
and flags **any reduction whose accumulator is narrower than 32 bits
while its operands descend from narrow (bf16/fp16/fp8/int8/…) values** —
including Pallas scratch accumulators, scan-carry running sums, unrolled
``acc += tap`` chains, and ``x.at[...].add`` scatter loops.

Lint surfaces:

* every ``shipped_kernels()`` registry entry, traced **twice** — once with
  its registered operand dtypes and once with every f32 operand swapped to
  bf16.  The swap is the regression probe: an accumulator that *follows*
  the operand dtype (``jnp.zeros(..., x.dtype)`` — the PR 7 pattern) is
  invisible at f32 and flagrant at bf16.
* both CNN backbones' traced forward+backward train step (the real
  program PSG/SLU/SMD run in), via abstract ``init_train_state`` +
  ``make_train_step`` tracing — nothing executes.
* the declared accumulator-dtype intent: ``dispatch.kernel_acc_dtypes()``
  records what each kernel *means* to accumulate in; any float-dtype
  ``ref-accum`` site that disagrees, or a shipped kernel with no declared
  intent, is a finding even when no narrow operand reaches it today.

Allowlist convention: ``ALLOWLIST`` maps a site-substring pattern to a
**non-empty justification string** (e.g. PSG's intentional int8 sign
votes, should one ever accumulate).  An empty justification raises — an
allowlist entry without a recorded *why* is how intentional exceptions
rot into unexamined ones.  Run as a module
(``python -m repro.analysis.precision_lint``) it exits nonzero on any
unallowlisted finding — that is the CI hook.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.dataflow import (DataflowResult, ReductionSite, analyze,
                                     acc_is_narrow)

# site-substring pattern -> justification.  Empty on main: every shipped
# surface accumulates in f32.  (Example shape, should a narrow accumulator
# ever be intentional:
#   "conv_grad_w_pallas/pallas": "int8 sign votes are saturating counters,"
#                               " not partial sums — Eq. (2) needs signs")
ALLOWLIST: Dict[str, str] = {}


@dataclass(frozen=True)
class PrecisionFinding:
    surface: str        # which lint surface produced it
    rule: str           # "narrow-accumulator" | "acc-intent" | "acc-intent-missing"
    site: str
    kind: str
    acc_dtype: str
    narrow_operands: Tuple[str, ...]
    message: str

    def __str__(self) -> str:
        return (f"{self.surface}: [{self.rule}] {self.site} "
                f"({self.kind}, acc={self.acc_dtype}): {self.message}")


def check_allowlist(allowlist: Dict[str, str]) -> None:
    """Every allowlist entry must carry a non-empty justification."""
    for pattern, why in allowlist.items():
        if not (isinstance(why, str) and why.strip()):
            raise ValueError(
                f"precision allowlist entry {pattern!r} has no "
                "justification — record why the narrow accumulator is "
                "intentional")


def _allowlisted(site: str, allowlist: Dict[str, str]) -> Optional[str]:
    for pattern in allowlist:
        if pattern in site:
            return pattern
    return None


def split_findings(findings: Sequence[PrecisionFinding],
                   allowlist: Optional[Dict[str, str]] = None
                   ) -> Tuple[List[PrecisionFinding], List[PrecisionFinding]]:
    """(unallowlisted, allowlisted) under a justified allowlist."""
    al = ALLOWLIST if allowlist is None else allowlist
    check_allowlist(al)
    out, suppressed = [], []
    for f in findings:
        (suppressed if _allowlisted(f.site, al) else out).append(f)
    return out, suppressed


def _hazard_findings(surface: str, result: DataflowResult
                     ) -> List[PrecisionFinding]:
    out = []
    for s in result.hazards():
        via = f" (narrow via {s.origin})" if s.origin else ""
        out.append(PrecisionFinding(
            surface=surface, rule="narrow-accumulator", site=s.site,
            kind=s.kind, acc_dtype=s.acc_dtype,
            narrow_operands=s.narrow_operands,
            message=f"accumulates {','.join(s.narrow_operands)}-descended "
                    f"operands in {s.acc_dtype}{via} — force a >=32-bit "
                    "accumulator (the PR 7 bug class)"))
    return out


def narrow_variant(args):
    """The registry entry's args with every f32 array swapped to bf16 —
    the probe that exposes dtype-following accumulators."""
    def swap(s):
        if getattr(s, "dtype", None) == jnp.float32 and s.shape:
            return jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        return s
    return jax.tree.map(swap, args)


def _float_ref_accums(result: DataflowResult) -> List[ReductionSite]:
    def is_float(name: str) -> bool:
        return name.startswith(("float", "bfloat"))
    return [s for s in result.sites
            if s.kind == "ref-accum" and is_float(s.acc_dtype)]


def lint_kernels() -> List[PrecisionFinding]:
    """Dataflow-lint every shipped kernel (registered + bf16-narrowed) and
    cross-check detected ref accumulators against the declared intent."""
    from repro.kernels.dispatch import kernel_acc_dtypes, shipped_kernels

    intents = kernel_acc_dtypes()
    findings: List[PrecisionFinding] = []
    for name, (fn, args) in shipped_kernels().items():
        base = name.split("[")[0]
        if base not in intents:
            findings.append(PrecisionFinding(
                surface=f"kernel:{name}", rule="acc-intent-missing",
                site=name, kind="registry", acc_dtype="?",
                narrow_operands=(),
                message="shipped kernel has no declared accumulator dtype "
                        "in dispatch.kernel_acc_dtypes()"))
            continue
        for variant, a in (("", args), ("~bf16", narrow_variant(args))):
            surface = f"kernel:{name}{variant}"
            res = analyze(fn, *a, name=surface)
            findings.extend(_hazard_findings(surface, res))
            if not variant:     # intent is checked on the shipped dtypes
                for s in _float_ref_accums(res):
                    if s.acc_dtype != intents[base]:
                        findings.append(PrecisionFinding(
                            surface=surface, rule="acc-intent",
                            site=s.site, kind=s.kind,
                            acc_dtype=s.acc_dtype,
                            narrow_operands=s.narrow_operands,
                            message=f"ref accumulator is {s.acc_dtype} but "
                                    f"dispatch declares {intents[base]}"))
    return findings


def _abstract_batch(exp, batch: int):
    S = jax.ShapeDtypeStruct
    if exp.task == "lm":
        return {"tokens": S((batch, exp.train.seq_len), jnp.int32),
                "labels": S((batch, exp.train.seq_len), jnp.int32)}
    return {"image": S((batch, 32, 32, 3), jnp.float32),
            "label": S((batch,), jnp.int32)}


def lint_experiment(exp, batch: Optional[int] = None
                    ) -> List[PrecisionFinding]:
    """Dataflow-lint one experiment's traced fwd+bwd train step."""
    from repro.training.train_step import init_train_state, make_train_step

    b = exp.train.global_batch if batch is None else batch
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(lambda k: init_train_state(k, exp), key)
    surface = f"train:{exp.model.name}"
    res = analyze(make_train_step(exp), state, _abstract_batch(exp, b),
                  name=surface)
    return _hazard_findings(surface, res)


def _default_experiments():
    from repro.configs.paper_cnns import mobilenetv2, resnet74
    return [resnet74(), mobilenetv2()]


def lint_all(exps=None, allowlist: Optional[Dict[str, str]] = None
             ) -> Tuple[List[PrecisionFinding], List[PrecisionFinding]]:
    """(unallowlisted, allowlisted) findings over every lint surface."""
    findings = lint_kernels()
    for exp in (exps if exps is not None else _default_experiments()):
        findings.extend(lint_experiment(exp))
    return split_findings(findings, allowlist)


def precision_report(exps=None) -> dict:
    """The BENCH_audit.json ``precision`` section."""
    findings, allowlisted = lint_all(exps)
    return {"findings": [str(f) for f in findings],
            "allowlisted": [str(f) for f in allowlisted],
            "passed": not findings}


def main() -> int:
    findings, allowlisted = lint_all()
    for f in findings:
        print(f)
    for f in allowlisted:
        print(f"allowlisted: {f}")
    print(f"precision lint: {len(findings)} finding(s), "
          f"{len(allowlisted)} allowlisted")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
