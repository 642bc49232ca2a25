"""Logical-axis sharding rules: FSDP + TP + EP + SP on one mesh.

Every parameter leaf is matched by key-path against a rule table that
assigns *logical* axes per dimension; logical axes map to mesh axes
("tp" -> model, "fsdp" -> data [+pod], "expert" -> model).  A logical axis
is silently dropped when the dimension is not divisible by the mesh axis
size (e.g. qwen2.5's 2 KV heads on a 16-way model axis) — the framework
guarantee is "always compiles, shards as much as divisibility allows",
which is the property the 40-cell dry-run certifies.

Layout conventions (models/layers.py): up-projections shard the output
axis over TP, down-projections the input axis — Megatron-style, so each
block needs only one reduce-scatter/all-reduce pair.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_abstract_mesh(shape, axes):
    """Device-free mesh for spec logic (tests, shape-only planning).

    ``AbstractMesh`` takes ``(axis_sizes, axis_names)``, the same order
    as :class:`jax.sharding.Mesh`'s shape and axes.
    """
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))

# ---------------------------------------------------------------------------
# activation-sharding hints (trace-time context, like core.psg.enable)
# ---------------------------------------------------------------------------

_act = threading.local()

# logical activation axes -> mesh axes
ACT_AXES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "vocab": ("model",),
    "heads": ("model",),
    "mlp": ("model",),
    "seq": ("model",),        # SP: sequence over model axis (training path)
    "tokens": ("pod", "data", "model"),   # flattened batch*seq (MoE groups)
}


@contextlib.contextmanager
def activation_sharding(mesh: Mesh):
    """Enable ``hint`` constraints while tracing under this mesh."""
    prev = getattr(_act, "mesh", None)
    _act.mesh = mesh
    try:
        yield
    finally:
        _act.mesh = prev


def hint(x, *logical_axes: Optional[str], free: bool = False):
    """with_sharding_constraint by logical activation axes; no-op when no
    mesh context is active (single-host smoke tests) or when an axis size
    does not divide the dimension.

    ``free=True`` maps unnamed dims to ``P.UNCONSTRAINED`` instead of
    replicated — use inside scan bodies where other dims carry model-axis
    sharding from the params (a plain ``None`` would FORCE replication,
    e.g. de-sharding Mamba's 64 internal heads: observed +40 GiB)."""
    mesh = getattr(_act, "mesh", None)
    if mesh is None:
        return x
    unnamed = P.UNCONSTRAINED if free else None
    spec = []
    used: set = set()
    for i, name in enumerate(logical_axes):
        if name is None:
            spec.append(unnamed)
            continue
        axes = tuple(a for a in ACT_AXES.get(name, ())
                     if a in mesh.axis_names and a not in used)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and x.shape[i] % size == 0 and size > 1:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            spec.append(unnamed)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def ctx_mesh_axis_size(name: str) -> int:
    """Size of a mesh axis in the active activation-sharding context (1 when
    tracing without a mesh — keeps model code mesh-agnostic)."""
    mesh = getattr(_act, "mesh", None)
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def ctx_data_axes(rows: int):
    """``(mesh, axes)``: the active mesh and its data axes (pod, data) of
    size > 1 when they split ``rows`` evenly, else ``(mesh, ())`` —
    ``(None, ())`` when tracing without a mesh."""
    mesh = getattr(_act, "mesh", None)
    if mesh is None:
        return None, ()
    axes = tuple(a for a in ACT_AXES["batch"]
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if size == 1 or rows % size:
        return mesh, ()
    return mesh, axes


def replicate(x):
    """Force a tensor replicated (all-gather on the wire) — used to place
    FSDP gathers on *int8 quantized codes* instead of bf16 weights (PSG
    int8-gather: the paper's low-precision data-movement insight applied to
    the collective roofline term).  No-op outside a mesh context."""
    mesh = getattr(_act, "mesh", None)
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(*([None] * x.ndim)))


def hint_batch(x, axis: int = 0):
    """Constrain only the batch axis (common case for activations inside
    scan bodies, where SPMD propagation into while-loop backwards is weak);
    other dims stay UNCONSTRAINED so param-derived shardings (e.g. TP'd
    head/state axes) survive."""
    spec: list = [None] * x.ndim
    spec[axis] = "batch"
    return hint(x, *spec, free=True)

# rule table: (path regex, candidate logical-axes specs).  Axes are
# right-aligned against the array shape (leading stacked 'units' axes get
# None), so the same rule covers scanned and unscanned params.  When a rule
# lists multiple candidates, the first whose named axes all divide is used
# (e.g. MoE weights: expert-parallel when num_experts % model == 0, else
# tensor-parallel within experts — grok's 8 experts on a 16-way model axis).
RULES: Tuple[Tuple[str, Any], ...] = (
    # embeddings / head
    (r"embed$",                ("tp_vocab", "fsdp")),
    (r"head$",                 ("fsdp", "tp_vocab")),
    # attention
    (r"attn/w[q]$",            ("fsdp", "tp", None)),
    (r"attn/w[kv]$",           ("fsdp", "tp", None)),
    (r"attn/wo$",              ("tp", None, "fsdp")),
    (r"attn/b[qkv]$",          ("tp", None)),
    # dense MLP
    (r"mlp/w_(up|gate)$",      ("fsdp", "tp")),
    (r"mlp/w_down$",           ("tp", "fsdp")),
    (r"mlp/b_up$",             ("tp",)),
    (r"mlp/b_down$",           (None,)),
    # MoE (expert parallelism over the model axis; TP fallback)
    (r"moe/router$",           (None, None)),
    (r"moe/w_(up|gate)$",      [("expert", "fsdp", None),
                                (None, "fsdp", "tp")]),
    (r"moe/w_down$",           [("expert", None, "fsdp"),
                                (None, "tp", "fsdp")]),
    (r"moe/shared/.*w_(up|gate)$", ("fsdp", "tp")),
    (r"moe/shared/.*w_down$",  ("tp", "fsdp")),
    # Mamba2
    (r"mamba/w_in$",           ("fsdp", "tp")),
    (r"mamba/w_out$",          ("tp", "fsdp")),
    (r"mamba/conv$",           (None, "tp")),
    (r"mamba/w_bc$",           ("fsdp", None)),
    (r"mamba/w_dt$",           ("fsdp", None)),
    # xLSTM
    (r"mlstm/w_in$",           ("fsdp", "tp")),
    (r"mlstm/w_out$",          ("tp", "fsdp")),
    (r"mlstm/w(q|k|v)$",       ("tp", None, None)),
    (r"mlstm/w_if$",           ("tp", None)),
    (r"slstm/w_g$",            ("fsdp", "tp")),
    (r"slstm/w_out$",          ("tp", "fsdp")),
    (r"slstm/r_g$",            ("tp_heads", None, None)),
    # norms, gates, scalars: replicated
    (r".*",                    ()),
)

LOGICAL_TO_MESH: Dict[str, Tuple[str, ...]] = {
    "tp": ("model",),
    "tp_vocab": ("model",),
    "tp_heads": ("model",),
    "expert": ("model",),
    "fsdp": ("data",),           # extended with 'pod' when multi-pod
}


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
    return "/".join(parts)


def logical_rules(path_s: str):
    for pat, axes in RULES:
        if re.search(pat, path_s):
            return axes
    return ()


def _candidates(logical):
    if isinstance(logical, list):
        return logical
    return [logical]


def _mesh_axes_for(name: str, mesh: Mesh, fsdp: bool):
    if name == "fsdp" and not fsdp:
        return ()
    mesh_axes = tuple(a for a in LOGICAL_TO_MESH.get(name, ())
                      if a in mesh.axis_names)
    if name == "fsdp":
        pod = tuple(a for a in ("pod",) if a in mesh.axis_names)
        mesh_axes = pod + mesh_axes
    return mesh_axes


def _try_spec(shape, logical, mesh: Mesh, fsdp: bool):
    """Returns (spec, all_named_axes_applied)."""
    ndim = len(shape)
    axes: list = [None] * ndim
    complete = True
    offset = ndim - len(logical)
    for i, name in enumerate(logical):
        if name is None or offset + i < 0:
            continue
        mesh_axes = _mesh_axes_for(name, mesh, fsdp)
        size = int(np.prod([mesh.shape[a] for a in mesh_axes])) if mesh_axes else 1
        if mesh_axes and size > 1 and shape[offset + i] % size == 0:
            axes[offset + i] = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
        elif name != "fsdp":
            complete = False
    return P(*axes), complete


def _spec_for(shape: Tuple[int, ...], logical, mesh: Mesh, fsdp: bool) -> P:
    """Right-align logical axes to shape; drop non-divisible shardings.
    For candidate lists, pick the first candidate whose non-fsdp axes all
    apply; fall back to the first candidate's partial application."""
    cands = _candidates(logical)
    if not cands or not cands[0]:
        return P(*([None] * len(shape)))
    first = None
    for cand in cands:
        spec, complete = _try_spec(shape, cand, mesh, fsdp)
        if first is None:
            first = spec
        if complete:
            return spec
    return first


def constrain_like_params(tree, fsdp: bool = True):
    """with_sharding_constraint a param-shaped tree (e.g. gradients, the
    microbatch grad-accumulator carry) to the rule-table shardings.  Without
    this, XLA tends to materialize *replicated* fp32 gradients for the
    embedding/LM-head (all-reduce instead of reduce-scatter) — multi-GiB per
    device at 128k vocabs.  No-op outside an activation-sharding context."""
    mesh = getattr(_act, "mesh", None)
    if mesh is None:
        return tree

    def one(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return leaf
        spec = _spec_for(tuple(leaf.shape), logical_rules(_path_str(path)),
                         mesh, fsdp)
        return jax.lax.with_sharding_constraint(leaf, spec)

    return jax.tree_util.tree_map_with_path(one, tree)


def param_shardings(params_shape, mesh: Mesh, fsdp: bool = True):
    """Pytree of NamedSharding matching a pytree of ShapeDtypeStruct/arrays."""
    def one(path, leaf):
        spec = _spec_for(tuple(leaf.shape), logical_rules(_path_str(path)),
                         mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_sharding(mesh: Mesh, batch: Any = 2, seq_shard: bool = False,
                   shape: Optional[Tuple[int, ...]] = None,
                   batch_axis: int = 0):
    """Data-batch sharding: the batch axis over pod+data, optionally the
    following (sequence) axis over model.  Axes that do not divide the
    dimension are dropped (e.g. the long_500k cell's global_batch=1).

    ``batch`` is either an int rank (the classic single-leaf call, with the
    optional concrete ``shape`` for divisibility checks) or a *batch pytree*
    (dict batches): rank and shape are then inferred per leaf — rank-1
    labels, rank-2 token batches, rank-4 NHWC CIFAR images, and their
    rank+1 chunk-stacked forms all resolve from one call.  ``batch_axis``
    points at the batch dimension (1 for chunk-stacked batches, where axis
    0 is the scan/K axis and stays unsharded — every device runs every
    scan step).
    """
    if isinstance(batch, int):
        return _leaf_batch_sharding(mesh, batch, shape, seq_shard, batch_axis)

    def one(leaf):
        shp = tuple(np.shape(leaf))
        return _leaf_batch_sharding(mesh, len(shp), shp, seq_shard,
                                    batch_axis)

    return jax.tree.map(one, batch)


def _leaf_batch_sharding(mesh: Mesh, ndim: int,
                         shape: Optional[Tuple[int, ...]],
                         seq_shard: bool, batch_axis: int) -> NamedSharding:
    if batch_axis >= ndim:
        return NamedSharding(mesh, P(*([None] * ndim)))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    if bsize <= 1 or (shape is not None and shape[batch_axis] % bsize != 0):
        batch_axes = ()
    axes: list = [None] * ndim
    axes[batch_axis] = (batch_axes if len(batch_axes) > 1 else
                        (batch_axes[0] if batch_axes else None))
    seq_axis = batch_axis + 1
    if seq_shard and "model" in mesh.axis_names and ndim > seq_axis:
        msize = mesh.shape["model"]
        if shape is None or shape[seq_axis] % msize == 0:
            axes[seq_axis] = "model"
    return NamedSharding(mesh, P(*axes))


def state_shardings(state_shape, mesh: Mesh, fsdp: bool = True):
    """Optimizer / SWA state mirrors parameter shardings (momentum etc. have
    identical shapes); scalars are replicated."""
    def one(path, leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = _spec_for(tuple(leaf.shape), logical_rules(_path_str(path)),
                         mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, state_shape)


def decode_state_shardings(state_shape, mesh: Mesh):
    """KV caches (B, T, nkv, hd): B over pod+data, T over model (ring-buffer
    slots shard cleanly; softmax reductions over the sharded T axis become
    small all-reduces XLA inserts).  Recurrent states: B over pod+data, the
    widest inner axis over model when divisible."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    b_axis = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    msize = mesh.shape.get("model", 1)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        path_s = _path_str(path)
        axes: list = [None] * leaf.ndim
        # leading axis may be the stacked units axis: detect batch position
        # by convention — decode states are (units, B, ...) after stacking
        bpos = 1 if "units" in path_s else 0
        if leaf.ndim > bpos and bsize > 1 and shape[bpos] % bsize == 0:
            axes[bpos] = b_axis
        if "kv" in path_s and leaf.ndim >= bpos + 3 and "model" in mesh.axis_names:
            # prefer head-sharding (TP attention, keeps softmax local);
            # fall back to ring-slot (T) sharding for small KV-head counts
            if shape[bpos + 2] % msize == 0:
                axes[bpos + 2] = "model"          # kv-heads axis
            elif shape[bpos + 1] % msize == 0:
                axes[bpos + 1] = "model"          # T axis
        elif leaf.ndim > bpos + 1 and "model" in mesh.axis_names:
            # recurrent state: shard the largest trailing axis if divisible
            rest = list(range(bpos + 1, leaf.ndim))
            if rest:
                j = max(rest, key=lambda i: shape[i])
                if shape[j] % msize == 0:
                    axes[j] = "model"
        return NamedSharding(mesh, P(*axes))

    return jax.tree_util.tree_map_with_path(one, state_shape)
