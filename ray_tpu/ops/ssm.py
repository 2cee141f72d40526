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
token, which a decode step runs over every slot.

Padding is exact, not approximate: where ``dt`` is 0 the decay is
``exp(0) = 1`` and the added term is 0, so the state passes through
untouched. A caller zeroes ``dt`` at positions that hold no token, and
the state after the block is the state after each row's last token.

``dt``, the decays, the state and every accumulation are float32; the
matmuls take operands in ``x``'s dtype (bf16 in a served model, float32
in a float32 one). ``b`` and ``c`` come in ``G`` groups, each shared by
``H / G`` consecutive heads. Plain ``jax.numpy`` and ``lax``: no kernel.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


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

    state, y = lax.scan(one_chunk, state.astype(jnp.float32),
                        (split(x), split(dt), split(b), split(c)))
    return jnp.moveaxis(y, 0, 1).reshape(n, t, heads, width), state


def ssm_step(x, dt, a, b, c, state):
    """One token a row. x [n, H, P]; dt [n, H] float32; a [H]; b, c
    [n, G, N]; state [n, H, P, N] float32. Returns (y [n, H, P] float32,
    the new state). Elementwise and a reduction, all float32: ``y`` is a
    sum over the new state's last axis (no matmul, so nothing of the
    state is rounded). The state need be read once and written once;
    XLA's lowering on a v5e reads it twice (PERF.md, section 5)."""
    heads = x.shape[1]
    b_h = _per_head(b, heads).astype(jnp.float32)               # [n, H, N]
    c_h = _per_head(c, heads).astype(jnp.float32)
    dtx = dt[..., None] * x.astype(jnp.float32)                 # [n, H, P]
    state = (jnp.exp(dt * a)[..., None, None] * state
             + dtx[..., None] * b_h[:, :, None, :])
    return jnp.sum(state * c_h[:, :, None, :], axis=-1), state


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
