"""Jaxpr dataflow engine: abstract interpretation for precision provenance.

``analyze(fn, *args)`` traces ``fn`` abstractly (arguments may be
:class:`jax.ShapeDtypeStruct` trees — nothing executes) and interprets the
jaxpr over a small per-value lattice:

* **narrow** — the set of sub-32-bit dtypes anywhere in the value's lineage
  (``bfloat16``/``float16``/fp8/``int8``/…).  Upcasting does *not* clear it:
  a value that was ever bf16 stays bf16-descended, which is exactly what
  the precision lint needs ("operands descend from quantized values").
* **depth** — how many reductions the value has passed through.
* **chain** — length of the current consecutive-additive-op run, used to
  recognize unrolled accumulation loops (``acc = acc + tap`` k² times)
  without flagging every residual add.
* **taints** — ``(tag, through_add)`` markers that implement cycle
  detection: scan carries and Pallas refs are seeded with a tag, additive
  ops flip ``through_add`` to True, and a tagged value arriving back at its
  own carry slot / ref *through an add* is an accumulation.
* **origin** — where narrowness first entered the lineage (for reports).

Every reduction the interpreter meets is recorded as a
:class:`ReductionSite` with its **accumulator dtype** (the output / carry /
ref dtype — the dtype partial sums actually live in):

=================  ========================================================
kind               emitted for
=================  ========================================================
``dot_general``    every contraction (accumulator = out dtype)
``conv``           ``conv_general_dilated``
``reduce_sum``     ``reduce_sum`` / ``reduce_window_sum``
``cumsum``         ``cumsum``
``scatter-add``    indexed accumulation (``x.at[...].add`` — the PR 7
                   reference-path bug class)
``add-chain``      an additive run crossing :data:`ADD_CHAIN_SITE` ops
                   (unrolled tap loops)
``scan-carry``     a ``scan``/``while`` carry that feeds back into itself
                   through an add (running sums, EMA)
``ref-accum``      a Pallas ref written with a value derived from its own
                   contents through an add (``acc_ref[...] += v``), or any
                   ``addupdate``
=================  ========================================================

Control flow: ``scan``/``while`` bodies run twice (seed, then fixpoint pass
that records sites), ``cond`` branches are all interpreted and their
outputs joined, ``pjit``/``custom_vjp``/``remat`` recurse transparently,
and ``pallas_call`` maps operands onto the kernel's input refs so the
lattice flows *into* kernel bodies (scratch refs start untainted with
their declared dtype — a bf16 scratch accumulator is caught as narrow).

The lint layers on top: :meth:`DataflowResult.hazards` returns the sites
whose accumulator is narrower than 32 bits while their operands descend
from narrow values — the bug class PR 7 fixed by hand.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Literal

# dtypes whose presence in a lineage marks a value "narrow-descended"
NARROW_DTYPES = frozenset({
    "bfloat16", "float16",
    "float8_e4m3fn", "float8_e5m2", "float8_e4m3fnuz", "float8_e5m2fnuz",
    "float8_e4m3b11_fnuz",
    "int8", "uint8", "int16", "uint16", "int4", "uint4",
})

# additive primitives: these flip the through_add taint flag and grow chains
_ADDITIVE = frozenset({"add", "add_any", "sub"})

# an additive run at least this long is an unrolled accumulation loop
ADD_CHAIN_SITE = 3

# reduction primitives -> site kind (accumulator = output dtype)
_REDUCE_SITES = {
    "dot_general": "dot_general",
    "conv_general_dilated": "conv",
    "reduce_sum": "reduce_sum",
    "reduce_window_sum": "reduce_sum",
    "cumsum": "cumsum",
    "scatter-add": "scatter-add",
    "scatter_add": "scatter-add",
}

# shape/layout ops that neither mix values nor break an additive run
_PASSTHROUGH = frozenset({
    "convert_element_type", "bitcast_convert_type", "broadcast_in_dim",
    "reshape", "squeeze", "expand_dims", "transpose", "slice",
    "dynamic_slice", "rev", "copy", "stop_gradient", "optimization_barrier",
    "device_put", "sharding_constraint",
})


def _dtype_name(dtype) -> str:
    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def _aval_dtype(aval) -> str:
    """Dtype name of an aval; sees through Pallas/state ref avals."""
    inner = getattr(aval, "inner_aval", aval)
    return _dtype_name(getattr(inner, "dtype", "void"))


def _is_ref(aval) -> bool:
    return hasattr(aval, "inner_aval") or type(aval).__name__.endswith("Ref")


def acc_is_narrow(dtype_name: str) -> bool:
    """True when partial sums in this dtype lose low-order contributions
    (any float/int accumulator under 32 bits)."""
    if dtype_name in NARROW_DTYPES:
        return True
    try:
        dt = np.dtype(dtype_name)
    except TypeError:
        return False
    return dt.kind in "fiu" and dt.itemsize < 4


@dataclass(frozen=True)
class Prov:
    """Per-value lattice element (see module doc)."""

    narrow: FrozenSet[str] = frozenset()
    depth: int = 0
    chain: int = 0
    taints: FrozenSet[Tuple[str, bool]] = frozenset()
    origin: str = ""


def join(*provs: Prov) -> Prov:
    narrow: FrozenSet[str] = frozenset()
    taints: FrozenSet[Tuple[str, bool]] = frozenset()
    depth = chain = 0
    origin = ""
    for p in provs:
        narrow |= p.narrow
        taints |= p.taints
        depth = max(depth, p.depth)
        chain = max(chain, p.chain)
        if p.narrow and not origin:
            origin = p.origin
    return Prov(narrow, depth, chain, taints, origin)


def _strip_taints(p: Prov, tags: Sequence[str]) -> Prov:
    ts = frozenset((t, f) for t, f in p.taints if t not in tags)
    return replace(p, taints=ts)


@dataclass(frozen=True)
class ReductionSite:
    """One reduction with the dtype its partial sums live in."""

    kind: str                           # see module table
    prim: str                           # jaxpr primitive name
    site: str                           # program path + name-stack scope
    acc_dtype: str                      # accumulator dtype name
    narrow_operands: Tuple[str, ...]    # narrow dtypes in operand lineage
    depth: int
    origin: str                         # where narrowness entered, "" if wide

    def __str__(self) -> str:
        ops = ",".join(self.narrow_operands) or "wide"
        via = f" (narrow via {self.origin})" if self.origin else ""
        return (f"{self.site}: [{self.kind}] accumulates {ops} operands "
                f"in {self.acc_dtype}{via}")


@dataclass
class DataflowResult:
    sites: List[ReductionSite] = field(default_factory=list)

    def hazards(self) -> List[ReductionSite]:
        """Sites accumulating narrow-descended operands in a sub-32-bit
        accumulator — the PR 7 bug class."""
        return [s for s in self.sites
                if s.narrow_operands and acc_is_narrow(s.acc_dtype)]


class _Interp:
    def __init__(self, name: str):
        self.name = name
        self.sites: Dict[Tuple, ReductionSite] = {}
        self.record = True
        self._ref_dtype: Dict[str, str] = {}
        self._ref_state: Dict[str, Prov] = {}
        self._uid = 0

    def _fresh(self, prefix: str) -> str:
        self._uid += 1
        return f"{prefix}{self._uid}"

    # -- environment ------------------------------------------------------

    def _read(self, env: Dict, atom) -> Prov:
        if isinstance(atom, Literal):
            dt = _dtype_name(getattr(atom.aval, "dtype", "void"))
            nar = frozenset({dt}) if dt in NARROW_DTYPES else frozenset()
            return Prov(narrow=nar, origin="literal" if nar else "")
        return env.get(atom, Prov())

    def _bind(self, env: Dict, var, prov: Prov, where: str) -> None:
        dt = _aval_dtype(var.aval)
        if dt in NARROW_DTYPES and dt not in prov.narrow:
            prov = replace(prov, narrow=prov.narrow | {dt},
                           origin=prov.origin or f"{where}:{dt}")
        env[var] = prov

    def _site(self, kind: str, prim: str, where: str, acc_dtype: str,
              operands: Prov) -> None:
        if not self.record:
            return
        key = (kind, prim, where, acc_dtype,
               tuple(sorted(operands.narrow)))
        if key not in self.sites:
            self.sites[key] = ReductionSite(
                kind=kind, prim=prim, site=where, acc_dtype=acc_dtype,
                narrow_operands=tuple(sorted(operands.narrow)),
                depth=operands.depth, origin=operands.origin)

    # -- interpretation ---------------------------------------------------

    def run_closed(self, closed: ClosedJaxpr, in_provs: Sequence[Prov],
                   path: str) -> List[Prov]:
        jx = closed.jaxpr
        env: Dict = {}
        for cv in jx.constvars:
            dt = _aval_dtype(cv.aval)
            nar = frozenset({dt}) if dt in NARROW_DTYPES else frozenset()
            env[cv] = Prov(narrow=nar, origin="const" if nar else "")
        for i, (v, p) in enumerate(zip(jx.invars, in_provs)):
            self._bind(env, v, p, f"{path}/in{i}")
        self.run_eqns(jx, env, path)
        return [self._read(env, ov) for ov in jx.outvars]

    def run_eqns(self, jx, env: Dict, path: str) -> None:
        for eqn in jx.eqns:
            self._eqn(env, eqn, path)

    def _where(self, eqn, path: str) -> str:
        stack = str(eqn.source_info.name_stack)
        return f"{path}/{stack}" if stack else path

    def _eqn(self, env: Dict, eqn, path: str) -> None:
        prim = eqn.primitive.name
        p = eqn.params
        where = self._where(eqn, path)

        if prim == "scan":
            self._loop(env, eqn, path, p["jaxpr"],
                       n_pre=p["num_consts"], n_carry=p["num_carry"],
                       prim="scan")
            return
        if prim == "while":
            self._loop(env, eqn, path, p["body_jaxpr"],
                       n_pre=p["cond_nconsts"] + p["body_nconsts"],
                       n_carry=len(eqn.outvars), prim="while")
            return
        if prim == "cond":
            ops = [self._read(env, a) for a in eqn.invars[1:]]
            outs: Optional[List[Prov]] = None
            for br in p["branches"]:
                bouts = self.run_closed(br, ops, path)
                outs = bouts if outs is None else \
                    [join(a, b) for a, b in zip(outs, bouts)]
            for ov, pr in zip(eqn.outvars, outs or []):
                self._bind(env, ov, pr, where)
            return
        if prim == "pallas_call":
            self._pallas(env, eqn, path)
            return
        if prim == "reduce":
            # generic lax.reduce: a sum iff its computation jaxpr adds
            comp = p.get("jaxpr")
            comp_j = comp.jaxpr if isinstance(comp, ClosedJaxpr) \
                else comp
            additive = any(e.primitive.name in _ADDITIVE
                           for e in getattr(comp_j, "eqns", []))
            ops = [self._read(env, a) for a in eqn.invars]
            opj = join(*ops) if ops else Prov()
            if additive:
                self._site("reduce_sum", prim, where,
                           _aval_dtype(eqn.outvars[0].aval), opj)
            out = Prov(narrow=opj.narrow, depth=opj.depth + 1, chain=0,
                       taints=frozenset((t, True) for t, _ in opj.taints)
                       if additive else opj.taints, origin=opj.origin)
            for ov in eqn.outvars:
                self._bind(env, ov, out, where)
            return
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in p:
                sub = p[key]
                closed = sub if isinstance(sub, ClosedJaxpr) \
                    else ClosedJaxpr(sub, ())
                ops = [self._read(env, a) for a in eqn.invars]
                outs = self.run_closed(closed, ops, path)
                for ov, pr in zip(eqn.outvars, outs):
                    self._bind(env, ov, pr, where)
                return

        if prim == "get":
            tag = self._ref_tag(env, eqn.invars[0])
            if tag is not None:
                content = self._ref_state.get(tag, Prov())
                out = Prov(narrow=content.narrow, depth=content.depth,
                           chain=0, taints=frozenset({(tag, False)}),
                           origin=content.origin)
                self._bind(env, eqn.outvars[0], out, where)
                return
        if prim in ("swap", "addupdate"):
            tag = self._ref_tag(env, eqn.invars[0])
            if tag is not None:
                val = self._read(env, eqn.invars[1])
                accumulates = (prim == "addupdate"
                               or (tag, True) in val.taints)
                if accumulates:
                    self._site("ref-accum", prim, where,
                               self._ref_dtype[tag], val)
                old = self._ref_state.get(tag, Prov())
                self._ref_state[tag] = join(
                    old, replace(val, taints=frozenset(), chain=0))
                for ov in eqn.outvars:
                    self._bind(env, ov, replace(old, chain=0,
                               taints=frozenset({(tag, False)})), where)
                return

        ops = [self._read(env, a) for a in eqn.invars]
        opj = join(*ops) if ops else Prov()

        if prim in _REDUCE_SITES:
            out_dt = _aval_dtype(eqn.outvars[0].aval)
            self._site(_REDUCE_SITES[prim], prim, where, out_dt, opj)
            out = Prov(narrow=opj.narrow, depth=opj.depth + 1, chain=0,
                       taints=frozenset((t, True) for t, _ in opj.taints),
                       origin=opj.origin)
            for ov in eqn.outvars:
                self._bind(env, ov, out, where)
            return

        if prim in _ADDITIVE:
            chain = max((o.chain for o in ops), default=0) + 1
            if chain == ADD_CHAIN_SITE:
                self._site("add-chain", prim, where,
                           _aval_dtype(eqn.outvars[0].aval), opj)
            out = Prov(narrow=opj.narrow, depth=opj.depth, chain=chain,
                       taints=frozenset((t, True) for t, _ in opj.taints),
                       origin=opj.origin)
            self._bind(env, eqn.outvars[0], out, where)
            return

        chain = opj.chain if prim in _PASSTHROUGH else 0
        out = replace(opj, chain=chain)
        for ov in eqn.outvars:
            self._bind(env, ov, out, where)

    # -- control flow -----------------------------------------------------

    def _loop(self, env: Dict, eqn, path: str, body, n_pre: int,
              n_carry: int, prim: str) -> None:
        invals = [self._read(env, a) for a in eqn.invars]
        pre, carries = invals[:n_pre], invals[n_pre:n_pre + n_carry]
        xs = invals[n_pre + n_carry:]
        where = self._where(eqn, path)
        tags = [self._fresh("carry") for _ in range(n_carry)]
        # while: eqn carries cond+body consts but the body only takes its own
        nb = len(body.jaxpr.invars) - n_carry - len(xs)
        body_pre = pre[len(pre) - nb:] if nb else []
        seeded = [join(c, Prov(taints=frozenset({(t, False)})))
                  for c, t in zip(carries, tags)]

        was = self.record
        self.record = False
        out1 = self.run_closed(body, body_pre + seeded + xs, path)
        self.record = was
        carried = [join(s, _strip_taints(o, tags))
                   for s, o in zip(seeded, out1[:n_carry])]
        outs = self.run_closed(body, body_pre + carried + xs, path)

        for i, (t, o) in enumerate(zip(tags, outs[:n_carry])):
            if (t, True) in o.taints:
                self._site("scan-carry", prim, where,
                           _aval_dtype(eqn.outvars[i].aval), o)
        for i, ov in enumerate(eqn.outvars):
            src = outs[i] if i < len(outs) else Prov()
            pr = _strip_taints(src, tags)
            if i < n_carry and (tags[i], True) in outs[i].taints:
                pr = replace(pr, depth=pr.depth + 1)
            self._bind(env, ov, replace(pr, chain=0), where)

    def _ref_tag(self, env: Dict, atom) -> Optional[str]:
        for t, _ in self._read(env, atom).taints:
            if t.startswith("ref"):
                return t
        return None

    def _pallas(self, env: Dict, eqn, path: str) -> None:
        p = eqn.params
        gm = p["grid_mapping"]
        inner = p["jaxpr"]
        jx = inner.jaxpr if isinstance(inner, ClosedJaxpr) else inner
        n_in, n_out = gm.num_inputs, gm.num_outputs
        kname = p.get("name", "kernel")
        kpath = f"{path}/pallas:{kname}"
        opvals = [self._read(env, a) for a in eqn.invars[-n_in:]] \
            if n_in else []

        env2: Dict = {}
        tag_of: Dict[int, str] = {}
        for i, v in enumerate(jx.invars):
            tag = self._fresh("ref")
            tag_of[i] = tag
            dt = _aval_dtype(v.aval)
            self._ref_dtype[tag] = dt
            content = opvals[i] if i < n_in else Prov()
            nar = frozenset({dt}) if dt in NARROW_DTYPES else frozenset()
            self._ref_state[tag] = join(
                replace(content, taints=frozenset(), chain=0),
                Prov(narrow=nar, origin=f"{kpath}/ref{i}:{dt}"
                     if nar else ""))
            env2[v] = Prov(taints=frozenset({(tag, False)}))
        self.run_eqns(jx, env2, kpath)

        where = self._where(eqn, path)
        for j, ov in enumerate(eqn.outvars):
            tag = tag_of.get(n_in + j)
            content = self._ref_state.get(tag, Prov()) if tag else Prov()
            self._bind(env, ov, replace(content, taints=frozenset()), where)


def analyze_jaxpr(closed: ClosedJaxpr,
                  name: str = "program") -> DataflowResult:
    """Interpret an already-traced program (see :func:`analyze`)."""
    it = _Interp(name)
    it.run_closed(closed, [Prov() for _ in closed.jaxpr.invars], name)
    return DataflowResult(sites=sorted(
        it.sites.values(), key=lambda s: (s.site, s.kind, s.acc_dtype)))


def analyze(fn, *args, name: str = "program", **kwargs) -> DataflowResult:
    """Trace ``fn`` abstractly (args may be ShapeDtypeStruct trees) and
    interpret the resulting jaxpr for precision provenance."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return analyze_jaxpr(closed, name=name)
