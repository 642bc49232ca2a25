"""Jaxpr cost walker: per-primitive FLOPs/bytes, attributed to named layers.

``jaxpr_costs(fn, *args)`` traces ``fn`` (abstract tracing only — arguments
may be :class:`jax.ShapeDtypeStruct` trees, nothing executes) and walks the
resulting jaxpr recursively, deriving per-primitive operation counts and
attributing every equation back to a *layer scope* read from the equation's
source-info name stack.  Model code tags layers with
``jax.named_scope("cost:<name>")`` (``models/resnet.py``,
``models/transformer.py``); the tag survives ``lax.scan`` bodies and the
``jvp``/``transpose`` wrappers of a gradient trace, so the same walker
attributes forward and train-step programs alike.

Counting semantics (MACs are the currency of ``core/cost.py``):

* ``dot_general`` — MACs = numel(out) x prod(lhs contracting dims).
* ``conv_general_dilated`` — MACs = numel(out) x prod(kernel spatial) x
  cin-per-group, **except** patch-extraction convolutions
  (``conv_general_dilated_patches``: identity kernel, one input channel per
  group, k*k*cin output channels) which move data rather than multiply it —
  those land in ``gather_flops``, never in MACs.  Counting them as compute
  would inflate a CIFAR stage-0 conv by k²/cout ≈ 56%.
* ``mul`` — tracked separately (``mul_flops``): the MobileNetV2 depthwise
  conv is an explicit broadcast-multiply + sum, so its MACs are exactly the
  multiply count of its layer scope.
* other elementwise / reduce ops — ``other_flops`` (one op per output
  element; reductions count their operand).
* control flow — ``scan`` bodies scale by trip count, ``while`` bodies by 1
  with ``unknown_trips`` flagged (mirroring ``launch/hlo_cost.py``'s
  explicit unknown-trip-count accounting), ``cond`` takes the most
  expensive branch, ``pjit``/``custom_vjp``/``remat`` recurse, and
  ``pallas_call`` kernels are walked once per grid step.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr

SCOPE_RE = re.compile(r"cost:([\w.\-]+)")
UNATTRIBUTED = ""

# one-output elementwise float ops: one flop per output element
_ELEMWISE = frozenset({
    "add", "sub", "div", "neg", "exp", "log", "tanh", "logistic", "rsqrt",
    "sqrt", "pow", "integer_pow", "max", "min", "abs", "sign", "floor",
    "ceil", "round", "cos", "sin", "erf", "expm1", "log1p", "add_any",
    "atan2", "cbrt", "clamp", "nextafter", "rem", "square",
})
# reductions: one flop per *operand* element
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "cumsum", "cumprod", "cummax", "cummin", "argmax", "argmin",
})
# pure data movement / metadata: zero flops, zero bytes charged
_FREE = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "convert_element_type", "bitcast_convert_type", "stop_gradient", "copy",
    "iota", "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not", "xor",
    "select_n", "is_finite", "sign", "device_put", "sharding_constraint",
    "optimization_barrier", "split", "concatenate", "pad", "slice",
    "dynamic_slice", "dynamic_update_slice", "rev", "gather", "scatter",
    "scatter-add", "program_id", "num_programs",
})


@dataclass
class OpCounts:
    """Operation counts of one attribution scope (or a whole program)."""

    dot_macs: float = 0.0       # dot_general contractions
    conv_macs: float = 0.0      # real conv_general_dilated contractions
    gather_flops: float = 0.0   # patch-extraction convs (data movement)
    mul_flops: float = 0.0      # elementwise multiplies
    other_flops: float = 0.0    # remaining elementwise/reduce work
    out_bytes: float = 0.0      # bytes written by non-metadata ops

    def macs(self) -> float:
        """MAC-bearing compute: contractions only (BN/activations excluded)."""
        return self.dot_macs + self.conv_macs

    def flops(self) -> float:
        return (2.0 * (self.dot_macs + self.conv_macs) + self.mul_flops
                + self.other_flops)

    def add(self, other: "OpCounts", scale: float = 1.0) -> None:
        self.dot_macs += scale * other.dot_macs
        self.conv_macs += scale * other.conv_macs
        self.gather_flops += scale * other.gather_flops
        self.mul_flops += scale * other.mul_flops
        self.other_flops += scale * other.other_flops
        self.out_bytes += scale * other.out_bytes

    def to_dict(self) -> Dict[str, float]:
        return {"dot_macs": self.dot_macs, "conv_macs": self.conv_macs,
                "gather_flops": self.gather_flops, "mul_flops": self.mul_flops,
                "other_flops": self.other_flops, "out_bytes": self.out_bytes}


@dataclass
class ProgramCosts:
    """Walk result: per-scope counts plus program-level flags."""

    by_scope: Dict[str, OpCounts] = field(default_factory=dict)
    unknown_trips: int = 0      # while loops whose trip count is not static

    def scope(self, tag: str) -> OpCounts:
        if tag not in self.by_scope:
            self.by_scope[tag] = OpCounts()
        return self.by_scope[tag]

    def total(self) -> OpCounts:
        t = OpCounts()
        for c in self.by_scope.values():
            t.add(c)
        return t

    def to_dict(self) -> Dict[str, Any]:
        return {"by_scope": {k: v.to_dict()
                             for k, v in sorted(self.by_scope.items())},
                "total": self.total().to_dict(),
                "unknown_trips": self.unknown_trips}


def scope_tag(eqn) -> str:
    """Innermost ``cost:<name>`` tag of an equation's name stack, or ''.

    Transform wrappers (``jvp(...)``, ``transpose(...)``, ``rematted(...)``)
    decorate but do not erase the scope, so the last match is the layer the
    primal computation belonged to.
    """
    m = SCOPE_RE.findall(str(eqn.source_info.name_stack))
    return m[-1] if m else UNATTRIBUTED


def _numel(aval) -> float:
    return float(math.prod(aval.shape)) if hasattr(aval, "shape") else 1.0


def _out_bytes(eqn) -> float:
    total = 0.0
    for v in eqn.outvars:
        aval = v.aval
        if hasattr(aval, "shape") and hasattr(aval, "dtype"):
            try:
                itemsize = np.dtype(aval.dtype).itemsize
            except TypeError:     # extended dtypes (PRNG keys): 4-word state
                itemsize = 16
            total += _numel(aval) * itemsize
    return total


def _dot_macs(eqn) -> float:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    k = 1.0
    for d in lhs_c:
        k *= lhs.shape[d]
    return _numel(eqn.outvars[0].aval) * k


def _conv_counts(eqn) -> Tuple[float, float]:
    """(conv_macs, gather_flops) of one conv_general_dilated equation."""
    dn = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    out_n = _numel(eqn.outvars[0].aval)
    cin_per_group = rhs.shape[dn.rhs_spec[1]]
    spatial = 1.0
    for d in dn.rhs_spec[2:]:
        spatial *= rhs.shape[d]
    groups = eqn.params.get("feature_group_count", 1)
    lhs_channels = lhs.shape[dn.lhs_spec[1]]
    macs_per_out = spatial * cin_per_group
    if cin_per_group == 1 and groups == lhs_channels and groups > 1:
        # conv_general_dilated_patches: depth-separated identity kernel that
        # *rearranges* the input into im2col rows — movement, not MACs.
        return 0.0, out_n * macs_per_out
    return out_n * macs_per_out, 0.0


def sub_jaxprs(eqn):
    """(closed_jaxpr, trip_multiplier) children of an equation, plus
    whether they are a branch set (``cond``) rather than a sequence.

    Public: the dataflow/hot-loop analyzers reuse this as the one place
    that knows where every higher-order primitive hides its sub-programs
    (scan/while/cond/pallas_call/pjit/custom_vjp/remat)."""
    prim = eqn.primitive.name
    p = eqn.params
    if prim == "scan":
        return [(p["jaxpr"], float(p["length"]))], False
    if prim == "while":
        # body once per trip; trips are not static in general — the caller
        # flags it (cond jaxpr cost is negligible and skipped).
        return [(p["body_jaxpr"], 1.0)], False
    if prim == "cond":
        return [(b, 1.0) for b in p["branches"]], True
    if prim == "pallas_call":
        gm = p["grid_mapping"]
        trips = float(math.prod(gm.grid)) if gm.grid else 1.0
        inner = p["jaxpr"]
        closed = ClosedJaxpr(inner, ()) \
            if not isinstance(inner, ClosedJaxpr) else inner
        return [(closed, trips)], False
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            sub = p[key]
            closed = sub if isinstance(sub, ClosedJaxpr) \
                else ClosedJaxpr(sub, ())
            return [(closed, 1.0)], False
    return [], False


def _walk(jaxpr, costs: ProgramCosts, scale: float,
          outer_scope: str) -> None:
    for eqn in jaxpr.eqns:
        tag = scope_tag(eqn) or outer_scope
        prim = eqn.primitive.name

        subs, is_branches = sub_jaxprs(eqn)
        if subs:
            if prim == "while":
                costs.unknown_trips += 1
            if is_branches:
                # max-cost branch: mirrors hlo_cost's conditional handling
                best, best_macs = None, -1.0
                for sub, _ in subs:
                    probe = ProgramCosts()
                    _walk(sub.jaxpr, probe, 1.0, tag)
                    t = probe.total()
                    key = (t.macs(), t.flops())
                    if best is None or key > best_macs:
                        best, best_macs = probe, key
                if best is not None:
                    costs.unknown_trips += best.unknown_trips
                    for s, c in best.by_scope.items():
                        costs.scope(s or tag).add(c, scale)
            else:
                for sub, trips in subs:
                    _walk(sub.jaxpr, costs, scale * trips, tag)
            continue

        c = costs.scope(tag)
        if prim == "dot_general":
            c.dot_macs += scale * _dot_macs(eqn)
            c.out_bytes += scale * _out_bytes(eqn)
        elif prim == "conv_general_dilated":
            macs, gather = _conv_counts(eqn)
            c.conv_macs += scale * macs
            c.gather_flops += scale * gather
            c.out_bytes += scale * _out_bytes(eqn)
        elif prim == "mul":
            c.mul_flops += scale * _numel(eqn.outvars[0].aval)
            c.out_bytes += scale * _out_bytes(eqn)
        elif prim in _ELEMWISE:
            c.other_flops += scale * _numel(eqn.outvars[0].aval)
            c.out_bytes += scale * _out_bytes(eqn)
        elif prim in _REDUCE:
            c.other_flops += scale * _numel(eqn.invars[0].aval)
            c.out_bytes += scale * _out_bytes(eqn)
        elif prim in _FREE:
            pass
        else:
            # unknown primitive: charge bytes only, never silent compute
            c.out_bytes += scale * _out_bytes(eqn)


def walk_jaxpr(closed: ClosedJaxpr) -> ProgramCosts:
    costs = ProgramCosts()
    _walk(closed.jaxpr, costs, 1.0, UNATTRIBUTED)
    return costs


def jaxpr_costs(fn, *args, **kwargs) -> ProgramCosts:
    """Trace ``fn`` abstractly and walk the program's cost.

    ``args``/``kwargs`` may be (trees of) arrays or
    :class:`jax.ShapeDtypeStruct` — nothing is executed or compiled.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return walk_jaxpr(closed)
