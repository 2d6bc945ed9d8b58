"""Logical axes -> PartitionSpec with divisibility-checked fallbacks.

Every model parameter / activation / cache tensor carries a tuple of logical
axis names (e.g. ``("embed", "heads", "head_dim")``).  A rule table maps each
logical name to an ordered list of *candidate* mesh placements; the first
candidate whose mesh-axis product divides the dimension size — and whose mesh
axes are not already taken by an earlier dimension of the same tensor — wins.
``None`` (replicate) is always a legal last resort.

Why candidates instead of a fixed map: the assigned archs are adversarial to
any single rule.  granite-34b has 1 kv head (cannot TP-shard heads), whisper
has 20 heads and a 51866 vocab (neither divides a 16-way model axis), and
long_500k decodes at global batch 1 (cannot DP-shard batch).  The fallback
chain keeps one rule table valid for every (arch x shape x mesh) cell instead
of 40 bespoke tables — the same move the paper makes when `tiled_matmul_auto`
picks tile factors per matrix instead of hardcoding them.

Mesh conventions (launch/mesh.py):
  * single-pod: ``("data", "model")`` = (16, 16)
  * multi-pod:  ``("pod", "data", "model")`` = (2, 16, 16); the ``pod`` axis
    crosses the slow DCN/ICI-pod boundary, so rules only ever put *batch*
    (pure DP) on it — parameters are FSDP-sharded over the intra-pod ``data``
    axis so their all-gathers never cross pods, and only the once-per-step
    gradient reduction does (where ``train/compression.py`` applies the
    paper's int8 trick).
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Candidate = Union[None, str, tuple]
AxisRules = Mapping[str, Sequence[Candidate]]

# --- rule tables -----------------------------------------------------------

# Training / prefill defaults: FSDP over `data`, TP over `model`, DP over
# (`pod`, `data`).
DEFAULT_RULES: AxisRules = {
    # activations
    "batch": (("pod", "data"),),
    "seq": (None,),
    "embed_act": (None,),
    # params: table below is ordered so a param's dims are tried in tensor
    # order — fallbacks engage only when an earlier dim failed (see module
    # docstring for the arch cases that need it).
    "vocab": ("model", None),
    "embed": ("data", None),            # FSDP axis
    "mlp": ("model", None),             # Megatron column/row split
    "heads": ("model", None),
    "kv_heads": ("model", None),
    "head_dim": ("model", None),        # engaged when heads/kv_heads fail
    "qkv": (None,),                     # fused-qkv minor dims
    "experts": ("model", None),         # expert parallelism
    "expert_mlp": (None,),
    "expert_cap": (("pod", "data"), None),  # dispatched token slots
    "state": (None,),                   # SSM state dim (small: 16..128)
    "inner": ("model", None),           # SSM d_inner (channel TP)
    "inner_heads": ("model", None),     # Mamba-2 head axis
    "conv_k": (None,),
    "dt_rank": (None,),
    "layers": (None,),                  # stacked-scan leading dim
    "img_seq": (None,),
    "frames": (None,),
    "norm": (None,),
    # KV-cache timeline (prefill fills it, decode extends it): TP shards
    # kv_heads when they divide, else the sequence (split-KV)
    "cache_seq": ("model", None),
    # Full-sequence attention activations (B, H, L, hd): heads carry TP
    # when they divide; otherwise the *sequence* does (context-parallel
    # attention — GSPMD all-gathers K/V per shard instead of psumming
    # (B, H, L, L) score tensors, the whisper/qwen 20/40-head fix).  Dim
    # order (batch, heads,
    # attn_seq, head_dim) encodes the fallback.
    "attn_seq": ("model", None),
}

# Sequence parallelism (32k prefill / long-context): activations carry their
# sequence dim on `model` between blocks; attention/scan internals gather it.
SP_RULES: AxisRules = {
    **DEFAULT_RULES,
    "seq": ("model", None),
}

# Decode: the KV cache is the resident tensor.  Batch over DP; cache heads
# over TP, falling back to *sequence* sharding of the cache (flash-decoding
# style split-KV: each model shard scans its stretch of the timeline and the
# softmax combines via psum) when kv heads don't divide — granite kv=1,
# h2o kv=8.  Dim order (batch, kv_heads, seq, head_dim) encodes the chain.
DECODE_RULES: AxisRules = {
    **DEFAULT_RULES,
    "batch": (("pod", "data"), None),
    "cache_seq": ("model", None),
    "kv_heads": ("model", None),
    # Decode reads every weight once per token: FSDP weight-gathers would
    # cost ~param-bytes of collective per step (measured on falcon decode,
    # §Perf iteration 3) — replicate across `data`, shard on `model` only.
    "embed": (None,),
}


def _axes_in_mesh(cand: Candidate, mesh: Mesh) -> tuple:
    """Normalize a candidate to a tuple of axes present in this mesh."""
    if cand is None:
        return ()
    if isinstance(cand, str):
        cand = (cand,)
    return tuple(a for a in cand if a in mesh.axis_names)


def logical_to_spec(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
) -> PartitionSpec:
    """Map one tensor's logical axes to a PartitionSpec on ``mesh``."""
    assert len(axes) == len(shape), (axes, shape)
    taken: set = set()
    out = []
    for name, size in zip(axes, shape):
        pick = None
        for cand in rules.get(name, (None,)) if name is not None else (None,):
            mesh_axes = _axes_in_mesh(cand, mesh)
            if not mesh_axes:       # None candidate or axis absent: replicate
                pick = None
                break
            if any(a in taken for a in mesh_axes):
                continue
            n = math.prod(mesh.shape[a] for a in mesh_axes)
            if n and size % n == 0:
                pick = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
                taken.update(mesh_axes)
                break
        out.append(pick)
    # strip trailing None for tidy specs
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, shape, mesh, rules))


def _is_axes_leaf(x) -> bool:
    """A logical-axes tuple: plain tuple of names/None (not a NamedTuple)."""
    return (
        isinstance(x, tuple)
        and not hasattr(x, "_fields")
        and all(e is None or isinstance(e, str) for e in x)
    )


def shardings_for_tree(
    axes_tree: Any,
    shape_tree: Any,
    mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
) -> Any:
    """NamedSharding pytree for (axes pytree, ShapeDtypeStruct pytree).

    ``axes_tree`` leaves are tuples of logical names; tuples are leaves here
    (matched positionally against the shape tree).
    """
    leaves_axes, treedef = jax.tree.flatten(axes_tree, is_leaf=_is_axes_leaf)
    leaves_shape = treedef.flatten_up_to(shape_tree)
    shardings = [
        named_sharding(a, s.shape, mesh, rules)
        for a, s in zip(leaves_axes, leaves_shape)
    ]
    return jax.tree.unflatten(treedef, shardings)


# --- activation-constraint context ------------------------------------------
#
# Model code annotates activations by logical axes unconditionally; the
# constraint engages only inside ``activate(mesh, rules)`` (used by the
# launchers/dry-run), and is a no-op in single-device unit tests.  The
# context is read at *trace* time, so it must wrap ``jit(...).lower()`` /
# the first call, not execution.

import contextlib
import contextvars
import functools

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_active", default=None
)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: AxisRules = DEFAULT_RULES):
    token = _ACTIVE.set((mesh, rules))
    try:
        with mesh:
            yield
    finally:
        _ACTIVE.reset(token)


_MANUAL_AXES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_manual_axes", default=frozenset()
)


def _manual_axes_here() -> set:
    """Mesh axes that are Manual in the current trace (inside shard_map):
    the abstract mesh's Manual axes plus the set our ``shard_map`` wrapper
    records while tracing its body."""
    am = jax.sharding.get_abstract_mesh()
    return set(_MANUAL_AXES.get()) | {
        n for n, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual
    }


def constrain(
    x: jax.Array,
    axes: Sequence[Optional[str]],
    rules: Optional[AxisRules] = None,
) -> jax.Array:
    """``with_sharding_constraint`` by logical axes, against the active mesh.

    No-op outside an ``activate(...)`` region so model code can annotate
    unconditionally.  Inside a shard_map manual region (e.g. the
    pod-compressed trainer), axes that are already Manual are dropped from
    the spec — they're physically fixed there.
    """
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, active_rules = active
    spec = logical_to_spec(axes, x.shape, mesh, rules or active_rules)
    manual = _manual_axes_here()
    if manual:
        def strip(entry):
            if entry is None:
                return None
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            kept = tuple(n for n in names if n not in manual)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]
        spec = PartitionSpec(*(strip(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` that records its manual axes for ``constrain``.

    ``axis_names`` is the set of *manual* axes; ``None`` (the jax default)
    means all mesh axes.
    """
    manual = (
        frozenset(mesh.axis_names) if axis_names is None
        else frozenset(axis_names)
    )

    @functools.wraps(f)
    def traced(*args, **kwargs):
        # Record the manual set for constrain()'s axis stripping: tracing
        # of the body happens inside this call, so the contextvar is live
        # exactly while sharding constraints inside ``f`` are staged.
        token = _MANUAL_AXES.set(frozenset(_MANUAL_AXES.get()) | manual)
        try:
            return f(*args, **kwargs)
        finally:
            _MANUAL_AXES.reset(token)

    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(
        traced, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kw,
    )


# --- detection fleet (replica mesh) -----------------------------------------

# The detection service's slot grids are (slots, H, W) batches: the only
# shardable axis is the slot axis, over the 1-D ("replica",) mesh of
# launch.mesh.make_replica_mesh — rows/columns stay whole (the Canny halo
# and the Hough vote read whole frames).
DETECTION_RULES: AxisRules = {
    "slots": ("replica", None),
    "row": (None,),
    "col": (None,),
}


def slot_sharding(mesh: Mesh, n_slots: int) -> NamedSharding:
    """NamedSharding splitting a (slots, H, W) grid's slot axis over the
    replica mesh (replicated fallback when slots don't divide it)."""
    return named_sharding(
        ("slots", "row", "col"), (n_slots, 1, 1), mesh, DETECTION_RULES,
    )


def shard_slots(batch, mesh: Mesh):
    """Place a host-side (slots, H, W) batch slot-sharded on ``mesh`` —
    the one explicit transfer of an SPMD detection dispatch (each device
    holds ``slots / n_replica`` frames; the frame-independent kernels
    then run without any cross-replica collective)."""
    import numpy as np
    arr = np.asarray(batch)
    return jax.device_put(arr, slot_sharding(mesh, arr.shape[0]))


def rules_for_shape(shape_kind: str) -> AxisRules:
    """Pick the rule table for a workload shape class.

    train_*   -> DEFAULT (FSDP+TP, batch DP)
    prefill_* -> SP (sequence-sharded activations between blocks)
    decode_* / long_* -> DECODE (cache-resident layout)
    """
    if shape_kind.startswith("prefill"):
        return SP_RULES
    if shape_kind.startswith(("decode", "long")):
        return DECODE_RULES
    return DEFAULT_RULES
