"""Decide ``correct``: the program's first chunk against the plain reference.

Set-up drives the program's own trainer, with the window's own call and
feed, through its first chunk of executed steps from weights the
benchmark made.  After the window the reference (``bench/reference``)
follows the same steps on the same batches from the same weights, and
these numbers are compared, each with a limit of its own
(``bench/limits/<cell>.json``):

* ``gate_first``: gap of the first step's mean SLU keep probability over
  the blocks (the program's ``slu_cost``; 1 where a model has no gate).
  The gate pools each block input over the whole batch, so the code
  flips that PSG's 8-bit forward quantization makes out of any rounding
  difference average away there, while a batch other than the
  reference's, or another precision, moves it;
* ``loss_first``: relative gap of the first step's loss (before any
  update: forward, SLU gates, BatchNorm, loss);
* ``loss_rest``: the largest relative loss gap over the chunk's other
  steps (after PSG or SGD updates);
* ``grad_norm_gap``: the optimizer's gradient buffer after the chunk (the
  last gradient as sign SGD gets it; the momentum sum under SGD), worst
  leaf, as the gap of the two norms over the reference's norm of that leaf
  or of the median leaf, whichever is larger;
* ``update_norm_gap``: the same measure of the parameters' change over
  the chunk; leaves whose first reference gradient is under a thousandth
  of the median leaf's are left out (they move by round-off alone);
* ``grad_median_gap``, ``update_median_gap``: the median over the leaves
  of the same per-leaf gaps, a number that one small leaf cannot move;
* ``state_norm_gap``: the same measure of the BatchNorm running
  statistics after the chunk (each step's forward leaves its batch
  statistics there), worst leaf; ``stem_state_gap``: the stem's alone,
  the layer that sees the images themselves;
* ``smd_steps``, ``slu_executed``, ``step_counter``: exact counts of
  disagreement in which steps SMD kept, how many blocks SLU ran per step,
  and the final step counter.

SLU's gate draws ``u`` and runs a block where ``u < p``.  The program's
``p`` differs from the reference's by its matmul rounding, so where
``|u - p|`` is within ``SLU_BAND`` either decision is right.  Where the
program ran another number of blocks than the reference in a step, the
reference tries the opposite decision at each such block and keeps the
one that runs the program's number of blocks with the nearest loss.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import numpy as np

SLU_BAND = 0.02
GRAD_FLOOR = 1e-3


def smd_schedule(seed: int, start: int, count: int, drop_prob: float,
                 enabled: bool) -> List[bool]:
    """Keep decisions of nominal steps ``start .. start+count-1``: a step
    is kept where a uniform draw keyed by (seed, step) is >= drop_prob."""
    if not enabled:
        return [True] * count
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 s))(np.arange(start,
                                                               start + count))
    return list(np.asarray(jax.vmap(jax.random.uniform)(keys)) >= drop_prob)


def nominal_steps(seed: int, start: int, executed: int, drop_prob: float,
                  enabled: bool) -> int:
    """Nominal steps from ``start`` that keep exactly ``executed`` steps,
    the last one kept, so every chunk is full."""
    if not enabled:
        return executed
    n, kept, block = 0, 0, max(4 * executed, 64)
    while True:
        keep = smd_schedule(seed, start + n, block, drop_prob, True)
        for k in keep:
            n += 1
            kept += bool(k)
            if kept == executed:
                return n
        # the next block of decisions


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(v, np.float64))) for p, v in flat}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Sequence[str]) -> Dict[str, float]:
    """Per leaf: |norm_prog - norm_ref| over max(norm_ref of the leaf,
    median leaf's norm_ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Sequence[str]) -> float:
    """Worst leaf of :func:`leaf_gaps`."""
    return max(leaf_gaps(prog, ref, keep).values())


def difference(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def chooser(prog_losses: Sequence[float], prog_exec: Sequence[float],
            n_slots: int):
    """Align SLU decisions within ``SLU_BAND`` of the draw (module doc)."""
    def count(res):
        return int(round(float(np.sum(np.asarray(res[3]["executed"],
                                                 np.float32)))))

    def choose(i, res, rerun):
        target = int(round(prog_exec[i] * n_slots))
        if n_slots <= 1 or count(res) == target:
            return res
        out = res[3]
        u = np.asarray(out["u"], np.float32)
        p = np.asarray(out["keep_p"], np.float32)
        best, best_gap = res, None
        for b in np.flatnonzero(np.abs(u - p) < SLU_BAND):
            alt = rerun(np.arange(n_slots) == b)
            if count(alt) != target:
                continue
            gap = abs(float(alt[3]["loss"]) - prog_losses[i])
            if best_gap is None or gap < best_gap:
                best, best_gap = alt, gap
        return best

    return choose


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers of the module doc.  ``prog`` and ``ref`` each hold
    ``losses``, ``keep_mean`` (mean keep probability per step),
    ``executed`` (blocks run per step), ``params_before``,
    ``params_after``, ``buffer`` (the optimizer's gradient buffer),
    ``state`` (the BatchNorm running statistics after the chunk) and
    ``step``; ``ref`` also ``grads_first`` and ``steps_kept`` and ``prog``
    ``steps`` (the nominal ids of its executed steps)."""
    pl, rl = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    rel = np.abs(pl - rl) / np.maximum(np.abs(rl), 1e-30)
    g = leaf_norms(ref["grads_first"])
    med = float(np.median(list(g.values())))
    moving = [k for k, v in g.items() if v >= GRAD_FLOOR * med]
    grad = leaf_gaps(leaf_norms(prog["buffer"]), leaf_norms(ref["buffer"]),
                     list(g))
    update = leaf_gaps(
        leaf_norms(difference(prog["params_after"], prog["params_before"])),
        leaf_norms(difference(ref["params_after"], ref["params_before"])),
        moving)
    ps, rs = leaf_norms(prog["state"]), leaf_norms(ref["state"])
    state = leaf_gaps(ps, rs, list(rs))
    stem = [k for k in rs if k.startswith("['stem")]
    out = {
        "gate_first": abs(float(prog["keep_mean"][0])
                          - float(ref["keep_mean"][0])),
        "loss_first": float(rel[0]),
        "loss_rest": float(np.max(rel[1:])) if len(rel) > 1 else 0.0,
        "grad_norm_gap": max(grad.values()),
        "update_norm_gap": max(update.values()),
        "grad_median_gap": float(np.median(list(grad.values()))),
        "update_median_gap": float(np.median(list(update.values()))),
        "state_norm_gap": max(state.values()),
        "stem_state_gap": norm_gap(ps, rs, stem) if stem else 0.0,
        "smd_steps": float(sum(a != b for a, b in
                               zip(prog["steps"], ref["steps_kept"]))
                           + abs(len(prog["steps"]) - len(ref["steps_kept"]))),
        "slu_executed": float(np.sum(np.abs(
            np.rint(prog["executed"]) - np.rint(ref["executed"])))),
        "step_counter": float(abs(int(prog["step"]) - int(ref["step"]))),
    }
    if not np.all(np.isfinite(pl)):
        out["loss_first"] = out["loss_rest"] = float("inf")
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): each compared number with its limit, in the
    limits file's order; a number that is not finite fails."""
    checks = {k: [numbers[k], float(v)] for k, v in limits.items()}
    ok = all(np.isfinite(n) and n <= lim for n, lim in checks.values())
    return bool(ok), checks
