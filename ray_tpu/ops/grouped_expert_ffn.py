"""The routed experts over the rows routed to them: one grouped matmul.

Reference: ABSENT from the reference repo (SURVEY.md §2c row EP). The
(token, choice) pairs of ``ops.moe.moe_ffn_dropless`` arrive SORTED by
held expert: ``load[e]`` rows for expert ``e``, one group after the other,
and behind the last group the pairs that belong to no held expert
(choices on another chip's experts, padding rows). An expert's part is
``act(rows @ up[e]) @ down[e]`` over its own group alone.

``grouped_matmul`` is the one Pallas kernel (``KERNEL_NAME`` in a trace),
called twice a layer: rows x the up stacks with the experts' activation
as its epilogue (bf16 hidden activations between the calls: a few MB
where the weights are a GB), then x the down stack in float32. What it
does that a grouped matmul padded group by group does not:

- rows come in tiles of ``ROW_TILE``; the grid's second axis walks the
  VISITS, one a (tile, group) pair that shares a row, in the sorted
  order: at most ``tiles + groups - 1`` of them, and of those only the
  ``count`` that hold a row do anything. Which tile and which group a
  visit is, and where the groups start, come through scalar prefetch
  (``group_visits``, from ``load``); the steps past ``count`` repeat the
  last visit's blocks, so nothing is fetched for them, and compute
  nothing. Tiles past the last held row are never visited; an expert
  that got no row is no visit and its weights stay unread;
- a tile that straddles groups is visited once a group, each visit
  writing only its group's rows (the output tile stays in VMEM across the
  consecutive visits and is merged under a row mask);
- the contraction is never split: a weight block is [K, tn] of one
  expert, ``tn`` as wide as ``WEIGHT_BLOCK_BYTES`` allows (the whole
  matrix at every width the benchmark has but one), and the grid runs
  (column block, visit) with the visits inside. Consecutive visits of one
  group keep the block index, so an expert's weights are read ONCE
  however many tiles its rows fill; the rows' tiles are read once a
  column block. With 20-130 rows an expert the kernel is bound by that
  one read of the touched experts' weights;
- the stacks go in WHOLE, [layers, experts, K, N] as a run of layers
  keeps them, with the layer's index through scalar prefetch into the
  weight blocks' index maps. A program that scans a run hands a kernel
  the layer's slice of each stack only as a copy of it (a fusion that
  writes [experts, K, N]: 0.8-1.5 GB a layer in and out again, as much
  as the kernel itself reads; found in ``serve-code-gen``'s trace, PR
  44), so the engine's prefill hands over the run's stacks and the index
  (``moe_ffn_dropless``'s ``layer``); a stack of one layer goes in as
  [1, experts, K, N].

Rows are independent in a matmul, so whatever the rows of no group hold
(and the rows past the array's end in a last partial tile) reaches no
other row; the kernel leaves them unwritten and the caller masks them.

``grouped_expert_ffn_reference`` is the plain sorted formulation, a loop
over the experts with each group picked by a row mask: what runs off the
TPU and what the tests hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "grouped_expert_ffn"
# rows of a tile: the MXU's own 128; smaller tiles stream fewer rows a
# weight load and save nothing
ROW_TILE = 128
# the most one step's weight blocks (one a stack) may hold; two such sets
# are in VMEM, the one computed on and the one in flight
WEIGHT_BLOCK_BYTES = 12 << 20
_VMEM_LIMIT_BYTES = 64 << 20
_LANES = 128


def hidden_activation(into, wi_gate, wi_up, gate_act=jax.nn.silu):
    """An expert's hidden activations in float32, by its form: ``into(w)``
    is the rows' float32 product with ``w`` (a stack, a matrix, a block of
    one). With a gate ``gate_act(x @ gate) * (x @ up)`` (SiLU: a SwiGLU;
    ``jax.nn.relu``: a ReGLU); without one (None) ``relu(x @ up) ** 2``."""
    if wi_gate is None:
        return jnp.square(jax.nn.relu(into(wi_up)))
    return gate_act(into(wi_gate)) * into(wi_up)


def group_visits(load, rows: int, tile: int = ROW_TILE):
    """The kernel's walk over ``rows`` sorted rows of which the first
    ``sum(load)`` lie in groups of ``load`` [H]: (``offsets`` [H + 1], the
    row each group starts at; ``groups`` [V] and ``tiles`` [V], the group
    and the row tile of visit v, V = tiles + H - 1; ``count`` [1], the
    visits that hold a row). A group's visits are the tiles from the one
    its first row lies in to the one its last row does; an empty group has
    none. Entries past ``count`` repeat the last visit."""
    h = load.shape[0]
    load = load.astype(jnp.int32)
    ends = jnp.cumsum(load)
    first_tile = lax.div(ends - load, tile)
    visits = jnp.where(load > 0, lax.div(ends + (tile - 1), tile)
                       - first_tile, 0)
    upto = jnp.cumsum(visits)
    count = upto[-1]
    v = jnp.minimum(jnp.arange(-(-rows // tile) + h - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # the group of visit v: as many groups end at or before it
    groups = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                                 dtype=jnp.int32), h - 1)
    tiles = first_tile[groups] + v - (upto - visits)[groups]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, groups, tiles, count.reshape(1)


def _column_block(k: int, n: int, stacks: int, itemsize: int) -> int:
    """Columns of a weight block [k, tn]: all ``n`` where the step's
    blocks fit ``WEIGHT_BLOCK_BYTES``, else the whole lanes that do, evened
    out over the blocks."""
    if stacks * k * n * itemsize <= WEIGHT_BLOCK_BYTES:
        return n
    most = max(WEIGHT_BLOCK_BYTES // (stacks * k * itemsize) // _LANES, 1)
    blocks = -(-n // (most * _LANES))
    return -(-n // (blocks * _LANES)) * _LANES


def _kernel(layer_ref, offsets_ref, groups_ref, tiles_ref, count_ref,  # SMEM
            x_ref, *refs, tile, epilogue, contract):
    *w_refs, o_ref = refs
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _():
        g = groups_ref[v]
        x = x_ref[...]
        y = epilogue(lambda w: lax.dot_general(
            x, w[...], (((1,), (contract,)), ((), ())),
            preferred_element_type=jnp.float32), *w_refs)
        row = tiles_ref[v] * tile + lax.broadcasted_iota(
            jnp.int32, y.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        # the other rows of the tile are another visit's (or no one's)
        o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def grouped_matmul(x, stacks, layer, visits, *, epilogue, out_dtype,
                   tile=ROW_TILE, interpret=False):
    """``epilogue(into, *(s[layer, e] for s in stacks))`` for every group
    e of the sorted rows ``x`` [M, K], ``into(w)`` the group's rows'
    float32 product with ``w``; ``stacks``: arrays [L, H, K, N] of which
    layer ``layer`` (a traced scalar) is read where it lies; ``visits``:
    ``group_visits`` of the groups' sizes at ``tile`` rows a tile. Returns
    [M, N] in ``out_dtype``; rows in no group are left as they were
    allocated."""
    m, k = x.shape
    n = stacks[0].shape[3]
    tn = _column_block(k, n, len(stacks), stacks[0].dtype.itemsize)
    # a stack [.., K, N] whose N is not whole lanes where its K is lies on
    # the chip as [.., N, K] (the compiler's own choice for a parameter of
    # that shape: K minor pads nothing). Handed over as it lies, its blocks
    # contracted over their last axis, it is read in place; asked for as
    # [.., K, N] it would be copied whole at every call
    as_it_lies = n % _LANES != 0 and k % _LANES == 0
    if as_it_lies:
        stacks = [jnp.swapaxes(s, 2, 3) for s in stacks]
        w_block = pl.BlockSpec(
            (None, None, tn, k),
            lambda j, v, lyr, off, grp, til, cnt: (lyr[0], grp[v], j, 0))
    else:
        w_block = pl.BlockSpec(
            (None, None, k, tn),
            lambda j, v, lyr, off, grp, til, cnt: (lyr[0], grp[v], 0, j))

    x_block = pl.BlockSpec((tile, k),
                           lambda j, v, lyr, off, grp, til, cnt: (til[v], 0))
    o_block = pl.BlockSpec((tile, tn),
                           lambda j, v, lyr, off, grp, til, cnt: (til[v], j))
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, epilogue=epilogue,
                          contract=1 if as_it_lies else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-n // tn), visits[1].shape[0]),
            in_specs=[x_block] + [w_block] * len(stacks),
            out_specs=o_block),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        # the visits in order on one core: an output tile is merged across
        # the consecutive visits that share it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name=KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *visits, x, *stacks)


@functools.partial(jax.jit,
                   static_argnames=("gate_act", "tile", "interpret"))
def grouped_expert_ffn_kernel(xs, load, wi_gate, wi_up, wo, layer, *,
                              gate_act=jax.nn.silu, tile=ROW_TILE,
                              interpret=False):
    """The kernel's launch; arguments and result as
    ``grouped_expert_ffn_reference``, but for the rows of no group, which
    hold anything. The stacks go in whole and the layer's index through
    scalar prefetch into the blocks' index maps: a program that scans a
    run of layers slices (copies) no layer's stacks out to feed it."""
    visits = group_visits(load, xs.shape[0], tile)
    if wi_gate is None:
        ups, hidden = (wi_up,), lambda into, up: hidden_activation(
            into, None, up)
    else:
        ups, hidden = (wi_gate, wi_up), functools.partial(
            hidden_activation, gate_act=gate_act)
    h = grouped_matmul(xs, ups, layer, visits, epilogue=hidden,
                       out_dtype=xs.dtype, tile=tile, interpret=interpret)
    return grouped_matmul(h, (wo,), layer, visits,
                          epilogue=lambda into, w: into(w),
                          out_dtype=jnp.float32, tile=tile,
                          interpret=interpret)


def grouped_expert_ffn_reference(xs, load, wi_gate, wi_up, wo, layer, *,
                                 gate_act=jax.nn.silu):
    """``xs`` [M, D]: rows sorted by held expert, ``load`` [H] of them a
    group; the stacks of a run of layers, of which this call is layer
    ``layer``'s: ``wi_gate`` ([L, H, D, F], or None for a form without a
    gate; ``gate_act``: the gate's activation), ``wi_up`` [L, H, D, F],
    ``wo`` [L, H, F, D]. Returns float32
    [M, D]: row i of group e is ``act(xs[i] @ up[e]) @ down[e]``, the
    hidden activations rounded to ``xs``'s type between the two; rows in
    no group are zero. A loop over the experts, each over all rows and
    kept where the row is its own."""
    wi_gate, wi_up, wo = (w if w is None else w[layer]
                          for w in (wi_gate, wi_up, wo))
    ends = jnp.cumsum(load)
    row = jnp.arange(xs.shape[0])

    def one(ys, e):
        h = hidden_activation(
            lambda w: jnp.dot(xs, w[e], preferred_element_type=jnp.float32),
            wi_gate, wi_up, gate_act)
        y = jnp.dot(h.astype(xs.dtype), wo[e],
                    preferred_element_type=jnp.float32)
        mine = (row >= ends[e] - load[e]) & (row < ends[e])
        return jnp.where(mine[:, None], y, ys), None

    ys, _ = lax.scan(one, jnp.zeros((xs.shape[0], wo.shape[2]), jnp.float32),
                     jnp.arange(load.shape[0]))
    return ys
