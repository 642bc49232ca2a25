"""Static Pallas kernel linter.

Lints every kernel registered through ``kernels/dispatch.shipped_kernels``
without executing anything: each kernel is traced abstractly
(``jax.make_jaxpr``), its ``pallas_call`` equations are located, and four
rules are checked against the grid mapping and the kernel jaxpr
(DESIGN.md §Analysis lists the rules and their rationale):

* ``vmem-budget`` — double-buffered input/output blocks plus scratch must
  fit the per-core VMEM budget (16 MiB).
* ``tile-alignment`` — Mosaic's own block rule: the last two block
  dimensions must each span the full array extent or align to the
  (8, 128) tile (rank-1 blocks: full, or a multiple of 128 * 32/bits).
  Small blocks get no exemption — a ``(1, 1)`` VMEM block of a larger
  array is refused by the compiler; scalars and per-tile flags belong in
  SMEM, whose whole-array blocks are exempt.  ``tests/test_tpu_compile.py``
  compiles the main-path kernels for a described chip and is the
  authority; this rule is the fast pre-check.
* ``coverage`` / ``oob-index`` — output BlockSpec index maps, enumerated
  over the full grid, must write every tile of the output lattice exactly
  (an uncovered tile is silent garbage memory) and no input/output index
  map may address a block outside its array.
* ``accumulator-discipline`` — a kernel with VMEM scratch accumulators and
  a reduction grid axis (an axis no output index map depends on) must gate
  accumulator init on ``program_id(axis) == 0`` and the finish/writeback on
  ``program_id(axis) == grid[axis] - 1`` via ``pl.when``; otherwise the
  revisited output tile reads stale or unwritten accumulator state.

``lint_shipped()`` is the CI entry point: it returns all findings across
the shipped-kernel registry, and the test suite asserts the list is empty.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Set, Tuple

import jax
import numpy as np
from jax import core as jcore
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

VMEM_BUDGET_BYTES = 16 * 1024 * 1024
SUBLANE, LANE = 8, 128
# coverage enumeration walks the full grid; past this it is skipped (no
# shipped kernel is near it — a representative registry shape should keep
# grids small on purpose).
MAX_GRID_POINTS = 8192


@dataclass(frozen=True)
class LintFinding:
    """One rule violation in one kernel."""

    kernel: str
    rule: str
    message: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.kernel}: {self.message}"


def _kernel_jaxpr(eqn):
    kj = eqn.params["jaxpr"]
    return kj.jaxpr if isinstance(kj, ClosedJaxpr) else kj


def _block_shape(bm) -> Tuple[int, ...]:
    """Block extents; squeezed dimensions (no ``block_size``) count as 1."""
    return tuple(int(getattr(d, "block_size", 1)) for d in bm.block_shape)


def _full_shape(bm) -> Tuple[int, ...]:
    return tuple(int(d) for d in bm.array_aval.shape)


def _in_smem(bm) -> bool:
    """A whole-array SMEM operand (scalars, flag vectors): no VMEM, no
    tiling rule."""
    from jax.experimental.pallas import tpu as pltpu
    return bm.block_aval.memory_space == pltpu.SMEM


def _eval_index_map(cj: ClosedJaxpr, point: Sequence[int]
                    ) -> Tuple[int, ...]:
    outs = jcore.eval_jaxpr(cj.jaxpr, cj.consts,
                            *[np.int32(p) for p in point])
    return tuple(int(o) for o in outs)


def _find_pallas_eqns(jaxpr) -> List:
    """All pallas_call equations in a jaxpr, recursing through call eqns."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for val in eqn.params.values():
            subs = val if isinstance(val, (list, tuple)) else (val,)
            for sub in subs:
                if isinstance(sub, ClosedJaxpr):
                    found.extend(_find_pallas_eqns(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    found.extend(_find_pallas_eqns(sub))
    return found


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _lint_vmem(name: str, gm, kj) -> List[LintFinding]:
    block_bytes = 0.0
    for bm in gm.block_mappings:
        if _in_smem(bm):
            continue
        dt = np.dtype(bm.array_aval.dtype)
        block_bytes += math.prod(_block_shape(bm)) * dt.itemsize
    scratch_bytes = 0.0
    n_io = gm.num_inputs + gm.num_outputs
    for v in kj.invars[n_io:]:
        aval = v.aval
        try:
            itemsize = np.dtype(aval.dtype).itemsize
        except TypeError:
            itemsize = 16
        scratch_bytes += math.prod(aval.shape) * itemsize
    vmem = 2.0 * block_bytes + scratch_bytes       # 2x: double buffering
    if vmem > VMEM_BUDGET_BYTES:
        return [LintFinding(name, "vmem-budget",
                            f"{vmem / 2**20:.1f} MiB (2x blocks + scratch) "
                            f"exceeds the {VMEM_BUDGET_BYTES // 2**20} MiB "
                            "VMEM budget")]
    return []


def _lint_alignment(name: str, gm) -> List[LintFinding]:
    findings = []
    for pos, bm in enumerate(gm.block_mappings):
        kind = "in" if pos < gm.num_inputs else "out"
        if _in_smem(bm):
            continue
        bs = _block_shape(bm)
        full = _full_shape(bm)
        lane = LANE if len(bs) >= 2 else \
            LANE * 4 // np.dtype(bm.array_aval.dtype).itemsize
        bad = []
        if bs[-1] % lane != 0 and bs[-1] != full[-1]:
            bad.append(f"last dim {bs[-1]} (want %{lane} or full {full[-1]})")
        if len(bs) >= 2 and bs[-2] % SUBLANE != 0 and bs[-2] != full[-2]:
            bad.append(f"dim -2 {bs[-2]} (want %{SUBLANE} or full {full[-2]})")
        if bad:
            findings.append(LintFinding(
                name, "tile-alignment",
                f"{kind}[{pos if kind == 'in' else pos - gm.num_inputs}] "
                f"block {bs} of {full}: " + "; ".join(bad)))
    return findings


def _lint_coverage(name: str, gm) -> List[LintFinding]:
    grid = tuple(int(g) for g in gm.grid)
    if not grid or math.prod(grid) > MAX_GRID_POINTS:
        return []
    findings = []
    points = list(itertools.product(*[range(g) for g in grid]))
    for pos, bm in enumerate(gm.block_mappings):
        is_out = pos >= gm.num_inputs
        opos = pos - gm.num_inputs
        cj = bm.index_map_jaxpr
        if len(cj.jaxpr.invars) != len(grid):
            continue                       # scalar-prefetch args: skip
        bs = _block_shape(bm)
        full = _full_shape(bm)
        nblocks = tuple(-(-f // b) for f, b in zip(full, bs))
        covered: Set[Tuple[int, ...]] = set()
        oob_reported = False
        for pt in points:
            idx = _eval_index_map(cj, pt)
            if not oob_reported and any(
                    i < 0 or i >= n for i, n in zip(idx, nblocks)):
                findings.append(LintFinding(
                    name, "oob-index",
                    f"{'out' if is_out else 'in'}"
                    f"[{opos if is_out else pos}] index map sends grid point "
                    f"{pt} to block {idx}, outside the "
                    f"{nblocks} block lattice of {full}"))
                oob_reported = True
            covered.add(idx)
        if is_out:
            lattice = set(itertools.product(*[range(n) for n in nblocks]))
            missing = len(lattice - covered)
            if missing == 0:
                continue
            findings.append(LintFinding(
                name, "coverage",
                f"out[{opos}] index map covers {len(covered)} of "
                f"{math.prod(nblocks)} output tiles over the full grid "
                f"({missing} tiles never written)"))
    return findings


def _output_depends_on_axis(gm, grid: Tuple[int, ...], axis: int) -> bool:
    base = [0] * len(grid)
    for bm in gm.block_mappings[gm.num_inputs:]:
        cj = bm.index_map_jaxpr
        if len(cj.jaxpr.invars) != len(grid):
            return True                    # unknown signature: be permissive
        lo = _eval_index_map(cj, base)
        hi_pt = list(base)
        hi_pt[axis] = grid[axis] - 1
        if _eval_index_map(cj, hi_pt) != lo:
            return True
    return False


def _lint_accumulators(name: str, gm, kj) -> List[LintFinding]:
    grid = tuple(int(g) for g in gm.grid)
    if gm.num_scratch_operands == 0 or not grid:
        return []
    red_axes = [a for a in range(len(grid))
                if grid[a] > 1 and not _output_depends_on_axis(gm, grid, a)]
    findings = []
    for axis in red_axes:
        # program_id(axis) vars at the kernel's top level
        pid_vars = {e.outvars[0] for e in kj.eqns
                    if e.primitive.name == "program_id"
                    and int(e.params.get("axis", -1)) == axis}
        # eq(program_id, literal) guards, following bool->int32 converts
        guards: Dict[int, Set] = {0: set(), grid[axis] - 1: set()}
        aliases: Dict = {}
        for e in kj.eqns:
            if e.primitive.name == "eq":
                lit, pid = None, None
                for iv in e.invars:
                    if isinstance(iv, Literal):
                        try:
                            lit = int(iv.val)
                        except (TypeError, ValueError):
                            lit = None
                    elif iv in pid_vars:
                        pid = iv
                if pid is not None and lit in guards:
                    guards[lit].add(e.outvars[0])
            elif e.primitive.name == "convert_element_type" \
                    and not isinstance(e.invars[0], Literal):
                aliases[e.outvars[0]] = e.invars[0]
        gated = {0: False, grid[axis] - 1: False}
        for e in kj.eqns:
            if e.primitive.name != "cond" or not e.invars:
                continue
            pred = e.invars[0]
            pred = aliases.get(pred, pred)
            for lit, vars_ in guards.items():
                if pred in vars_:
                    gated[lit] = True
        if not gated[0]:
            findings.append(LintFinding(
                name, "accumulator-discipline",
                f"reduction axis {axis} (grid {grid}): no pl.when-gated "
                f"init on program_id({axis}) == 0 — the first grid step "
                "reads uninitialized scratch"))
        if not gated[grid[axis] - 1]:
            findings.append(LintFinding(
                name, "accumulator-discipline",
                f"reduction axis {axis} (grid {grid}): no pl.when-gated "
                f"finish on program_id({axis}) == {grid[axis] - 1} — the "
                "output tile is written before the reduction completes"))
    return findings


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_jaxpr(closed: ClosedJaxpr, name: str = "kernel"
               ) -> List[LintFinding]:
    """Lint every pallas_call inside an already-traced program."""
    findings: List[LintFinding] = []
    for eqn in _find_pallas_eqns(closed.jaxpr):
        gm = eqn.params["grid_mapping"]
        kj = _kernel_jaxpr(eqn)
        findings += _lint_vmem(name, gm, kj)
        findings += _lint_alignment(name, gm)
        findings += _lint_coverage(name, gm)
        findings += _lint_accumulators(name, gm, kj)
    return findings


def lint_kernel(fn, *args, name: str = "kernel") -> List[LintFinding]:
    """Trace ``fn`` abstractly (ShapeDtypeStruct args allowed) and lint it."""
    return lint_jaxpr(jax.make_jaxpr(fn)(*args), name=name)


def lint_shipped() -> List[LintFinding]:
    """Lint the whole shipped-kernel registry (CI gate; [] = clean)."""
    from repro.kernels.dispatch import shipped_kernels

    findings: List[LintFinding] = []
    for name, (fn, args) in shipped_kernels().items():
        findings += lint_kernel(fn, *args, name=name)
    return findings
