"""Selective state-space recurrence (Mamba-2's SSD), in two forms.

One head keeps a state ``S`` [P, N] (``P`` the head's width, ``N`` the
state size) and at each token, with a step ``dt`` > 0, a decay rate ``a``
< 0, the token's ``x`` [P], ``b`` [N] and ``c`` [N]:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t        y_t = S_t c_t

``ssm_scan`` runs it over a block of tokens in CHUNKS (arXiv:2405.21060,
section 6): inside a chunk the outputs are a masked, decayed ``c b^T``
against the tokens' ``dt x`` (matmuls), across chunks the state is
carried, one step a chunk. It starts from a given state and hands back
the state after the block. ``ssm_step`` is the recurrence itself for one
token. ``ssm_state_step`` is what a decode step runs: the same one token
over the slots' states where they lie, in the STACKED array a serving
engine holds them in ([L, slots, H, P, N]), at one layer.

Padding is exact, not approximate: where ``dt`` is 0 the decay is
``exp(0) = 1`` and the added term is 0, so the state passes through
untouched. A caller zeroes ``dt`` at positions that hold no token, and
the state after the block is the state after each row's last token.

``dt``, the decays, the state and every accumulation are float32; the
matmuls take operands in ``x``'s dtype (bf16 in a served model, float32
in a float32 one). ``b`` and ``c`` come in ``G`` groups, each shared by
``H / G`` consecutive heads. The scan, the one-token update and the
convolution are plain ``jax.numpy`` and ``lax``.

``ssm_state_step`` on a program LOWERED for a TPU is one Pallas kernel
(``ssm_state_step_kernel``), where the state's shape meets its rule
(``state_kernel_engages``: a float32 state, ``N`` whole lanes, ``P`` whole
sublanes): XLA lowers the plain formulation to two fusions that read a
layer's state twice and write it once; the kernel moves it once each way.

- The stacked array stays where it is: the kernel's blocks are cut from
  it at [layer, slot, a block of heads] (the layer index comes through
  scalar prefetch into the block's index map), and its output IS its
  input (``input_output_aliases``). Handing the kernel a layer's slice
  would be a copy of the layer in and an update-slice out: the third
  pass by another name.
- The grid walks (slot, block of heads); Pallas' own pipeline fetches the
  next block and writes the last one back while this one is computed.
  A block is at most ``_BLOCK_BYTES`` of state; in and out, double
  buffered, four of them lie in VMEM.
- Per head: ``S' = d S + dtx (x) b``, elementwise in float32, and ``y =
  sum_n S' c`` with no matmul, so nothing of the state is rounded. ``d =
  exp(dt a)`` is a scalar a head (SMEM, scalar prefetch); a block's
  ``dtx`` [heads of the block, P] is transposed in VMEM so that a head's
  is a column, broadcast over lanes; ``b`` and ``c`` come in their
  groups, a head reads its group's row. All of these are computed
  outside (kilobytes a slot).
- ``y`` is made without a reduction over lanes: the lane tiles of ``S'
  c`` are added one on another, the [P, 128] that is left is laid on its
  side (one transpose) and its rows are added, which leaves the head's
  ``y`` along the lanes, a row of the block's [heads, P]. The cross-lane
  unit is what the body has least of: it serves the lane broadcasts of
  ``dtx`` OR a reduction over lanes a tile under a block's copies, not
  both. With both (the body until PR 66) a layer-step at 32-tile heads
  ([128, 256], Falcon-H1) still hid under its copies, and at 8-tile
  heads ([64, 128], Nemotron-H, granite-4.0-h) took 0.967 ms where the
  copies alone take 0.85; this body takes 0.857 there and serves every
  shape of the rule (``scripts/sweep_state_kernel.py``; PERF.md,
  Findings, PR 66).
- An inactive slot's block is copied through as it is, bit for bit (the
  pipeline writes every block back), and its ``y`` is zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scopes

STATE_KERNEL_NAME = "ssm_state_step"
_BLOCK_BYTES = 1 << 20      # of state a grid step moves each way
_BLOCK_MAX_BYTES = 3 << 20  # the most it may: four blocks lie in VMEM


def _per_head(grouped, heads: int, axis: int = -2):
    """The group axis [.., G, ..] -> a head axis [.., H, ..]: head j
    takes group j // (H / G)."""
    return jnp.repeat(grouped, heads // grouped.shape[axis], axis=axis)


def ssm_scan(x, dt, a, b, c, state, *, chunk: int = 128):
    """The recurrence over ``t`` tokens of each row, from ``state``.

    x [n, t, H, P]; dt [n, t, H] float32, 0 where the position holds no
    token; a [H] float32, negative; b, c [n, t, G, N]; state [n, H, P, N]
    float32. ``t`` is a multiple of ``chunk`` or shorter than it (one
    shorter chunk). Returns (y [n, t, H, P] float32, the state after the
    block [n, H, P, N] float32)."""
    n, t, heads, width = x.shape
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"{t} tokens are no whole number of {q}-token "
                         "chunks")
    chunks = t // q
    op = x.dtype                      # the matmuls' operand type

    def split(v):                     # [n, t, ...] -> [chunks, n, q, ...]
        return jnp.moveaxis(v.reshape(n, chunks, q, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(s_in, inp):
        x_c, dt_c, b_c, c_c = inp         # [n, q, ...]
        # log-decay from the chunk's start to each token, inclusive
        cs = jnp.cumsum(dt_c * a, axis=1)                       # [n, q, H]
        cs_h = cs.transpose(0, 2, 1)                            # [n, H, q]
        dtx = (dt_c[..., None] * x_c.astype(jnp.float32)).astype(op)
        # inside the chunk: token i takes from token j <= i what j added,
        # decayed over j+1..i
        scores = jnp.einsum("nigs,njgs->ngij", c_c, b_c,
                            preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.where(
            causal, cs_h[:, :, :, None] - cs_h[:, :, None, :],
            -jnp.inf))                                          # [n,H,i,j]
        mixed = (_per_head(scores, heads, axis=1) * decay).astype(op)
        y = jnp.einsum("nhij,njhp->nihp", mixed, dtx,
                       preferred_element_type=jnp.float32)
        # from the state at the chunk's start, decayed to each token
        c_h = _per_head(c_c, heads)                             # [n,q,H,N]
        y += jnp.exp(cs)[..., None] * jnp.einsum(
            "nihs,nhps->nihp", c_h, s_in.astype(op),
            preferred_element_type=jnp.float32)
        # the state at the chunk's end: the start's, decayed over the
        # whole chunk, and what each token added, decayed to the end
        to_end = jnp.exp(cs[:, -1:, :] - cs)                    # [n, q, H]
        b_h = (_per_head(b_c, heads).astype(jnp.float32)
               * to_end[..., None]).astype(op)
        s_out = (jnp.exp(cs[:, -1, :])[..., None, None] * s_in
                 + jnp.einsum("njhp,njhs->nhps", dtx, b_h,
                              preferred_element_type=jnp.float32))
        return s_out, y

    with jax.named_scope(scopes.SSM_SCAN):
        state, y = lax.scan(one_chunk, state.astype(jnp.float32),
                            (split(x), split(dt), split(b), split(c)))
        return jnp.moveaxis(y, 0, 1).reshape(n, t, heads, width), state


def ssm_step(x, dt, a, b, c, state):
    """One token a row. x [n, H, P]; dt [n, H] float32; a [H]; b, c
    [n, G, N]; state [n, H, P, N] float32. Returns (y [n, H, P] float32,
    the new state). Elementwise and a reduction, all float32: ``y`` is a
    sum over the new state's last axis (no matmul, so nothing of the
    state is rounded). The state need be read once and written once;
    XLA's lowering on a v5e reads it twice, which is why a decode step
    goes through ``ssm_state_step`` and its kernel."""
    heads = x.shape[1]
    b_h = _per_head(b, heads).astype(jnp.float32)               # [n, H, N]
    c_h = _per_head(c, heads).astype(jnp.float32)
    dtx = dt[..., None] * x.astype(jnp.float32)                 # [n, H, P]
    state = (jnp.exp(dt * a)[..., None, None] * state
             + dtx[..., None] * b_h[:, :, None, :])
    return jnp.sum(state * c_h[:, :, None, :], axis=-1), state


def _block_heads(heads: int, head_bytes: int) -> int:
    """Heads a grid step moves: whole groups of 8 (a tile of ``dtx`` and
    of ``y`` is 8 heads by ``P``), the most that divide ``heads`` within
    ``_BLOCK_BYTES`` of state, and 8 at the least; where ``heads`` is no
    multiple of 8, all of them."""
    fits = [k for k in range(8, heads + 1, 8)
            if heads % k == 0 and k * head_bytes <= _BLOCK_BYTES]
    return max(fits) if fits else (8 if heads % 8 == 0 else heads)


def state_kernel_engages(states) -> bool:
    """The rule, from the stacked state's shape and dtype (the array, or
    anything with its ``shape`` and ``dtype``): whether
    ``ssm_state_step`` over it is the kernel on a TPU. A float32 state
    [L, slots, H, P, N] whose ``N`` is whole lanes and ``P`` whole
    sublanes (a head's state is then whole float32 tiles), and whose
    heads go in blocks that four of fit the kernel's VMEM."""
    if len(states.shape) != 5 or states.dtype != jnp.float32:
        return False
    heads, width, size = states.shape[2:]
    head_bytes = 4 * width * size
    return (size % 128 == 0 and width % 8 == 0
            and _block_heads(heads, head_bytes) * head_bytes
            <= _BLOCK_MAX_BYTES)


def ssm_state_step_reference(x, dt, a, b, c, states, layer, active):
    """The plain formulation: the layer's states sliced out of the stack,
    ``ssm_step``, an inactive slot's kept by a ``where``, the layer
    written back. What the kernel is held to, and what every platform
    but the TPU (and every shape outside the rule) runs."""
    old = states[layer]
    y, new = ssm_step(x, dt, a, b, c, old)
    keep = active[:, None, None, None]
    return y, states.at[layer].set(
        jnp.where(keep, new.astype(states.dtype), old))


def _state_kernel(layer_ref, active_ref, decay_ref,            # SMEM
                  dtx_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *,
                  heads, per_group):
    """One (slot, block of heads); see the module docstring. dtx_ref,
    y_ref [hb, P]; b_ref, c_ref [G, 1, N]; s_ref, o_ref [hb, P, N]."""
    del layer_ref                     # the index maps read it
    hb, _, size = s_ref.shape
    slot = pl.program_id(0)
    first = pl.program_id(1) * hb
    live = active_ref[slot] != 0

    @pl.when(live)
    def _():
        dtx = dtx_ref[...].T                                    # [P, hb]
        for j in range(hb):
            group = (first + j) // per_group
            new = (decay_ref[slot * heads + first + j] * s_ref[j]
                   + dtx[:, j:j + 1] * b_ref[group])
            o_ref[j] = new
            # y = sum_n new c: lane tile on lane tile, then [P, 128] on
            # its side and its rows added, which leaves y along the lanes
            weighed = new * c_ref[group]
            rows = weighed[:, :128]
            for k in range(128, size, 128):
                rows = rows + weighed[:, k:k + 128]
            y_ref[j:j + 1, :] = jnp.sum(rows.T, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _state_call(body, x, dt, a, b, c, states, layer, active, *,
                interpret=False):
    """The launch of ``body`` (``_state_kernel``, or a sweep's stand-in
    for it) over the stack; arguments as ``ssm_state_step``."""
    n, heads, width = x.shape
    groups, size = b.shape[1:]
    hb = _block_heads(heads, 4 * width * size)
    decay = jnp.exp(dt * a).reshape(-1)                         # [n * H]
    dtx = dt[..., None] * x.astype(jnp.float32)                 # [n, H, P]
    b = b.astype(jnp.float32)[:, :, None, :]                    # [n,G,1,N]
    c = c.astype(jnp.float32)[:, :, None, :]

    def at_layer(s, h, layer_ref, *_):
        return layer_ref[0], s, h, 0, 0

    state_block = pl.BlockSpec((None, None, hb, width, size), at_layer)
    head_block = pl.BlockSpec((None, hb, width), lambda s, h, *_: (s, h, 0))
    group_block = pl.BlockSpec((None, groups, 1, size),
                               lambda s, h, *_: (s, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(body, heads=heads, per_group=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n, heads // hb),
            in_specs=[head_block, group_block, group_block, state_block],
            out_specs=[head_block, state_block]),
        out_shape=[jax.ShapeDtypeStruct((n, heads, width), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 6 (after the three prefetched scalars and dtx, b, c) is
        # the stacked state, and it is output 1: updated in place
        input_output_aliases={6: 1},
        interpret=interpret, name=STATE_KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      decay, dtx, b, c, states)


def ssm_state_step_kernel(x, dt, a, b, c, states, layer, active, *,
                          interpret=False):
    """The kernel's launch; arguments as ``ssm_state_step``."""
    return _state_call(_state_kernel, x, dt, a, b, c, states, layer, active,
                       interpret=interpret)


def ssm_state_step(x, dt, a, b, c, states, layer, active):
    """One token a slot at one layer of the slots' stacked states. x [n,
    H, P]; dt [n, H] float32; a [H]; b, c [n, G, N]; ``states`` [L, n,
    H, P, N]; ``layer`` a scalar; ``active`` [n] bool. Slot i's state at
    [layer, i] advances one token if ``active[i]`` and is otherwise left
    bit for bit as it was; every other layer's is untouched. Returns (y
    [n, H, P] float32, an inactive slot's row unspecified; the stacked
    array). A caller that donates ``states`` gets it back in place.

    Outside the rule (``state_kernel_engages``) this IS the plain
    formulation, called directly. Within it the two lowerings are the
    module's own functions, not closures made a call, so the programs of
    an engine trace them once."""
    with jax.named_scope(scopes.SSM_STEP):
        if not state_kernel_engages(states):
            return ssm_state_step_reference(x, dt, a, b, c, states, layer,
                                            active)
        return lax.platform_dependent(
            x, dt, a, b, c, states, layer, active,
            tpu=ssm_state_step_kernel, default=ssm_state_step_reference)


def causal_conv(x, tail, weight, bias):
    """Depthwise causal convolution of length ``K`` over ``x`` [n, t, C]
    behind the ``K - 1`` positions ``tail`` [n, K-1, C] that precede it
    (zeros at a sequence's start): position i sees i-K+1..i. weight
    [C, K], bias [C] or None. Float32 accumulation; returns float32
    [n, t, C]. ``K`` shifted multiply-adds: for a depthwise filter of 4
    nothing beats them."""
    k = weight.shape[-1]
    t = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(seq[:, i:i + t].astype(jnp.float32) * w[:, i]
              for i in range(k))
    return out if bias is None else out + bias.astype(jnp.float32)


def last_rows(x, tail, lengths):
    """The ``K - 1`` positions that precede position ``lengths`` of each
    row of ``x`` [n, t, C] laid behind ``tail`` [n, K-1, C]: the tail a
    convolution needs to go on from each row's last token (positions
    before the row's start come from ``tail``)."""
    keep = tail.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    idx = lengths[:, None] + jnp.arange(keep, dtype=lengths.dtype)[None]
    return jnp.take_along_axis(seq, idx[..., None], axis=1)
