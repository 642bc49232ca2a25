"""Hot-loop lint: the chunk program's contract, verified statically.

``training/loop.make_chunk_step`` compiles K executed train steps into one
device program — the repo's entire throughput story (DESIGN.md §Loop)
rests on that program having no hidden per-step host round-trips.  This
pass traces the chunk abstractly (``jax.make_jaxpr`` over
ShapeDtypeStruct trees — nothing runs) and checks every rule of
``training.loop.CHUNK_CONTRACT``:

==========================  ===============================================
rule                        check
==========================  ===============================================
``no-host-callback``        no callback/infeed/outfeed primitive anywhere
                            in the traced chunk (recursively, through
                            scan/cond/pjit/pallas bodies) — a
                            ``jax.debug.print`` inside the scanned body is
                            one host sync per step, the thing the chunk
                            loop exists to avoid
``static-trip-count``       the top level is a ``lax.scan`` whose static
                            ``length`` equals the chunk's K; any ``while``
                            in the program is a finding (unknown trips)
``shape-stable-body``       tracing at K and K+1 yields the same primitive
                            histogram — a Python-value-dependent operand
                            that bakes K into the *body* would recompile
                            per chunk length
``device-resident-metrics`` every metric leaf comes back stacked
                            ``(K, ...)`` (the per-step values stay on
                            device; the caller syncs once per boundary)
``no-donation-default``     the default lowering carries no
                            ``input_output_alias``, and
                            ``Trainer(donate_chunk_state=...)`` defaults
                            False (donation breaks the pinned bit-parity
                            with the per-step loop)
==========================  ===============================================

Run as a module (``python -m repro.analysis.hotloop_lint``) it lints the
chunk program for both registered task families (a CIFAR CNN and the
smoke LM) and exits nonzero on any finding — that is the CI hook.
"""
from __future__ import annotations

import inspect
from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr

from repro.analysis.jaxpr_cost import sub_jaxprs

# primitives that round-trip to the host when executed
_CALLBACK_MARKERS = ("callback",)
_CALLBACK_PRIMS = frozenset({"infeed", "outfeed", "debug_print"})


@dataclass(frozen=True)
class HotloopFinding:
    rule: str           # a CHUNK_CONTRACT entry
    site: str
    message: str

    def __str__(self) -> str:
        return f"{self.site}: [{self.rule}] {self.message}"


def _is_callback(prim: str) -> bool:
    return prim in _CALLBACK_PRIMS or any(m in prim
                                          for m in _CALLBACK_MARKERS)


def _walk_prims(jx, path: str, out: List[Tuple[str, str]]) -> None:
    for eqn in jx.eqns:
        prim = eqn.primitive.name
        out.append((prim, f"{path}/{prim}"))
        subs, _ = sub_jaxprs(eqn)
        for sub, _trips in subs:
            _walk_prims(sub.jaxpr, f"{path}/{prim}", out)


def _all_prims(closed: ClosedJaxpr, name: str) -> List[Tuple[str, str]]:
    out: List[Tuple[str, str]] = []
    _walk_prims(closed.jaxpr, name, out)
    return out


def _abstract_chunk_args(exp, K: int):
    """(state, batches, step_increment) ShapeDtypeStruct trees for the
    chunk program — nothing is allocated."""
    from repro.training.train_step import init_train_state

    S = jax.ShapeDtypeStruct
    key = S((2,), jnp.uint32)
    state = jax.eval_shape(lambda k: init_train_state(k, exp), key)
    B = exp.train.global_batch
    if exp.task == "lm":
        batches = {"tokens": S((K, B, exp.train.seq_len), jnp.int32),
                   "labels": S((K, B, exp.train.seq_len), jnp.int32)}
    else:
        batches = {"image": S((K, B, 32, 32, 3), jnp.float32),
                   "label": S((K, B), jnp.int32)}
    return state, batches, S((K,), jnp.int32)


def lint_program(chunk_fn, args, K: int, name: str = "chunk",
                 donate_argnums: Tuple[int, ...] = ()
                 ) -> List[HotloopFinding]:
    """Check one chunk-shaped program against CHUNK_CONTRACT (sans the
    Trainer-signature rule — see :func:`lint_trainer_default`).

    ``donate_argnums`` exists for fixtures: the contract's default is no
    donation, and passing a non-empty tuple here must produce a finding.
    """
    findings: List[HotloopFinding] = []
    closed = jax.make_jaxpr(chunk_fn)(*args)

    # no-host-callback
    for prim, site in _all_prims(closed, name):
        if _is_callback(prim):
            findings.append(HotloopFinding(
                "no-host-callback", site,
                f"'{prim}' inside the chunk program — one host round-trip "
                "per step re-creates the per-step loop's sync cost"))

    # static-trip-count: the top level must be a scan of static length K …
    top_scans = [e for e in closed.jaxpr.eqns
                 if e.primitive.name == "scan"]
    if not any(e.params.get("length") == K for e in top_scans):
        findings.append(HotloopFinding(
            "static-trip-count", name,
            f"no top-level lax.scan of static length K={K} — the chunk "
            "must be one statically-shaped scanned program"))
    # … and nothing anywhere may loop an unknown number of times
    for prim, site in _all_prims(closed, name):
        if prim == "while":
            findings.append(HotloopFinding(
                "static-trip-count", site,
                "while loop inside the chunk — trip count is not static "
                "(poisons the HLO cost audit, defeats AOT scheduling)"))

    # shape-stable-body: same primitive mix at K and K+1
    def bump(s, lead=K):
        if hasattr(s, "shape") and s.shape and s.shape[0] == lead:
            return jax.ShapeDtypeStruct((lead + 1,) + s.shape[1:], s.dtype)
        return s
    state, batches, incs = args
    args2 = (state, jax.tree.map(bump, batches), bump(incs))
    closed2 = jax.make_jaxpr(chunk_fn)(*args2)
    h1 = Counter(p for p, _ in _all_prims(closed, name))
    h2 = Counter(p for p, _ in _all_prims(closed2, name))
    if h1 != h2:
        diff = {p: (h1.get(p, 0), h2.get(p, 0))
                for p in set(h1) | set(h2) if h1.get(p) != h2.get(p)}
        findings.append(HotloopFinding(
            "shape-stable-body", name,
            f"primitive mix changes with K ({K} vs {K + 1}): {diff} — a "
            "Python-value-dependent operand is baking the chunk length "
            "into the body (recompiles per chunk)"))

    # device-resident-metrics: every metric leaf stacked (K, ...)
    _, metrics = jax.eval_shape(chunk_fn, *args)
    for path, leaf in jax.tree_util.tree_flatten_with_path(metrics)[0]:
        if not (getattr(leaf, "shape", ()) and leaf.shape[0] == K):
            findings.append(HotloopFinding(
                "device-resident-metrics",
                f"{name}/metrics{jax.tree_util.keystr(path)}",
                f"metric leaf has shape {getattr(leaf, 'shape', ())}, "
                f"expected leading chunk axis ({K}, ...) — per-step values "
                "must stay device-resident until the chunk boundary"))

    # no-donation-default: the documented default lowering never aliases.
    # Donation shows as tf.aliasing_output / jax.buffer_donor attrs in the
    # StableHLO text (input_output_alias is the post-compile HLO spelling).
    text = jax.jit(chunk_fn, donate_argnums=donate_argnums
                   ).lower(*args).as_text()
    if any(marker in text for marker in
           ("input_output_alias", "tf.aliasing_output", "jax.buffer_donor")):
        findings.append(HotloopFinding(
            "no-donation-default", name,
            "lowered chunk carries input_output_alias — donation is "
            "opt-in only (XLA CPU rewrites the scanned body in place and "
            "breaks bit-parity with the per-step loop; DESIGN.md §Loop)"))
    return findings


def lint_trainer_default() -> List[HotloopFinding]:
    """``Trainer(donate_chunk_state=...)`` must default False."""
    from repro.training.trainer import Trainer

    sig = inspect.signature(Trainer.__init__)
    param = sig.parameters.get("donate_chunk_state")
    if param is None or param.default is not False:
        return [HotloopFinding(
            "no-donation-default", "Trainer.__init__",
            f"donate_chunk_state default is "
            f"{None if param is None else param.default!r}, documented "
            "contract is False")]
    return []


def lint_chunk(exp, K: int = 3) -> List[HotloopFinding]:
    """Lint one experiment's real ``make_chunk_step`` program."""
    from repro.training.loop import make_chunk_step

    args = _abstract_chunk_args(exp, K)
    name = f"chunk:{exp.model.name}"
    return (lint_program(make_chunk_step(exp), args, K, name=name)
            + lint_trainer_default())


def _default_experiments():
    from repro.configs import smoke_experiment
    from repro.configs.paper_cnns import cnn_model
    from repro.core.config import E2TrainConfig, Experiment, TrainConfig

    cnn = Experiment(
        model=cnn_model("resnet14", 14), e2=E2TrainConfig(),
        train=TrainConfig(global_batch=8, lr=0.1, total_steps=100,
                          optimizer="sgdm"),
        task="cifar_cnn")
    return [cnn, smoke_experiment("llama3_8b")]


def lint_all(exps=None, K: int = 3) -> List[HotloopFinding]:
    findings: List[HotloopFinding] = []
    for exp in (exps if exps is not None else _default_experiments()):
        findings.extend(lint_chunk(exp, K=K))
    return findings


def hotloop_report(exps=None) -> dict:
    """The BENCH_audit.json ``hotloop`` section."""
    findings = lint_all(exps)
    return {"findings": [str(f) for f in findings],
            "passed": not findings}


def main() -> int:
    findings = lint_all()
    for f in findings:
        print(f)
    print(f"hotloop lint: {len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
