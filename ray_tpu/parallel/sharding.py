"""Logical-axis sharding rules → GSPMD partition specs.

The TPU-native replacement for the reference's wrapper-level sharded
parallelism (DDP/FSDP wraps in ``train/torch/train_loop_utils.py:74,246``):
instead of wrapping modules, every array in the model pytree carries *logical*
axis names, and a rule table maps logical axes onto mesh axes. Changing the
parallelism strategy = swapping the rule table; the model code never changes.

Logical axes used by the model library:

    batch    — per-example batch dim        → dp/fsdp (data parallel)
    seq      — sequence/token dim           → sp (sequence/context parallel)
    embed    — model (d_model) dim          → fsdp sharding of activations/params
    heads    — attention heads              → tp
    kv_heads — kv heads (GQA)               → tp
    mlp      — FFN hidden dim               → tp
    vocab    — vocabulary dim               → tp
    expert   — MoE expert dim               → ep
    layers   — stacked layer dim            → pp (pipeline parallel)
    stage    — pipeline stage dim           → pp
    (None)   — replicated
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Axes(tuple):
    """Marker type for a logical-axes annotation leaf. Distinguishable from
    namedtuples (e.g. optax states) when used as a pytree leaf predicate."""

    __slots__ = ()


def is_axes_leaf(x) -> bool:
    """True for annotation leaves: an ``Axes`` marker, or a plain tuple of
    axis entries (str/None/tuple-of-str). Namedtuple containers (e.g. optax
    states) are NOT leaves even though they subclass tuple."""
    if isinstance(x, Axes):
        return True
    if isinstance(x, tuple) and not hasattr(x, "_fields"):
        return all(
            e is None or isinstance(e, str)
            or (isinstance(e, (tuple, list))
                and all(isinstance(s, str) for s in e))
            for e in x
        )
    return False


@dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical axis name to mesh axis (or tuple of mesh axes,
    or None for replicated)."""

    batch: Any = ("dp", "fsdp")
    seq: Any = None
    embed: Any = None
    heads: Any = None
    kv_heads: Any = None
    mlp: Any = None
    vocab: Any = None
    expert: Any = None
    layers: Any = None
    stage: Any = None

    def mesh_axes(self, logical: tuple) -> P:
        out = []
        for ax in logical:
            if ax is None:
                out.append(None)
            else:
                out.append(getattr(self, ax))
        return P(*out)

    def with_overrides(self, **kw) -> "ShardingRules":
        return replace(self, **kw)


# --- presets (the §2c parallelism inventory as one-liners) ---

# Pure data parallel: params replicated, batch split.
DP_RULES = ShardingRules(batch=("dp", "fsdp"))

# Fully-sharded data parallel (ZeRO-3 analog): params/grads/optimizer sharded
# on fsdp axis; batch split over dp×fsdp.
FSDP_RULES = ShardingRules(batch=("dp", "fsdp"), embed="fsdp")

# Megatron-style tensor parallel: heads/mlp/vocab split on tp.
TP_RULES = ShardingRules(batch=("dp", "fsdp"), heads="tp", kv_heads="tp",
                         mlp="tp", vocab="tp")

# FSDP × TP (the common 2D layout for 7B+ on a slice).
FSDP_TP_RULES = ShardingRules(
    batch=("dp", "fsdp"), embed="fsdp", heads="tp", kv_heads="tp", mlp="tp",
    vocab="tp",
)

# + sequence parallel: activations sharded along seq on the sp axis.
FSDP_TP_SP_RULES = FSDP_TP_RULES.with_overrides(seq="sp")

# MoE: experts split on ep, everything else as FSDP×TP.
MOE_RULES = FSDP_TP_RULES.with_overrides(expert="ep")

# Pipeline parallel: the stacked layer dim split over pp (contiguous layer
# groups = stages), everything else FSDP×TP (tp entries drop out on meshes
# without a tp axis via _filter_spec_for_mesh).
PP_FSDP_RULES = FSDP_TP_RULES.with_overrides(layers="pp")

PRESETS = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "tp": TP_RULES,
    "fsdp_tp": FSDP_TP_RULES,
    "fsdp_tp_sp": FSDP_TP_SP_RULES,
    "moe": MOE_RULES,
    "pp_fsdp": PP_FSDP_RULES,
}


def _filter_spec_for_mesh(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes the mesh doesn't have (so FSDP_TP rules work on a
    dp-only mesh: tp entries become replicated), and drop repeated uses of a
    mesh axis (first dim wins): one array can map each mesh axis to at most
    one positional dimension — e.g. activations [batch(dp,fsdp), embed(fsdp)]
    keep fsdp on batch and replicate embed."""
    names = set(mesh.axis_names)
    used: set = set()

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = []
            for e in entry:
                if e in names and e not in used:
                    used.add(e)
                    kept.append(e)
            return tuple(kept) if kept else None
        if entry in names and entry not in used:
            used.add(entry)
            return entry
        return None

    return P(*(keep(e) for e in spec))


def logical_sharding(logical: tuple, mesh: Mesh, rules: ShardingRules) -> NamedSharding:
    """NamedSharding for one array annotated with logical axis names."""
    spec = _filter_spec_for_mesh(rules.mesh_axes(logical), mesh)
    return NamedSharding(mesh, spec)


def loss_layout(mesh: Mesh, rules: ShardingRules,
                vocab_size: int) -> tuple[tuple, tuple]:
    """How the fused loss lies on the mesh: ``(rows, vocab_axes)``, the
    mesh axes that split the hidden state's batch and its seq (a tuple
    each) and those that split the vocabulary inside the loss: those the
    rules split it over already, then those that split the rows. Every
    device then takes each chunk of rows whole against its own slice of
    the head, contracting over all of the model dimension, and what
    crosses the mesh has the rows' shape, never the logits'.
    ``vocab_axes`` is ``()`` where the rows lie on one device or the axes'
    size does not divide the vocabulary: the loss is then left to the
    partitioner as it stands. Axes of one device are left out of both."""
    spec = logical_sharding(("vocab", "batch", "seq"), mesh, rules).spec
    by_dim = [tuple(a for a in ((e,) if isinstance(e, str) else e or ())
                    if mesh.shape[a] > 1) for e in spec]
    rows = tuple(by_dim[1:])
    vocab_axes = tuple(a for axes in by_dim for a in axes)
    if (not any(rows)
            or vocab_size % math.prod(mesh.shape[a] for a in vocab_axes)):
        vocab_axes = ()
    return rows, vocab_axes


def tree_shardings(logical_tree, mesh: Mesh, rules: ShardingRules):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.
    ``logical_tree`` leaves are tuples like ("embed", "mlp") or ``Axes``."""
    return jax.tree.map(
        lambda logical: logical_sharding(tuple(logical), mesh, rules),
        logical_tree,
        is_leaf=is_axes_leaf,
    )


def shard_tree(tree, logical_tree, mesh: Mesh, rules: ShardingRules):
    """Device-put a pytree according to its logical annotations."""
    shardings = tree_shardings(logical_tree, mesh, rules)
    return jax.device_put(tree, shardings)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, rules: ShardingRules, ndim: int = 2,
                   *, shard_seq: bool = True) -> NamedSharding:
    """Sharding for an input batch [batch, seq, ...]: batch axis split per
    rules, sequence split if sp is active, rest replicated.

    ``shard_seq=False`` keeps the seq dim replicated — used for raw token
    batches of length S+1 (the shifted-target column makes S+1 typically
    indivisible by sp; ring attention's shard_map introduces the seq
    sharding inside the step instead)."""
    logical = ("batch", "seq" if shard_seq else None) + (None,) * (ndim - 2)
    return logical_sharding(logical[:max(ndim, 0)], mesh, rules)
