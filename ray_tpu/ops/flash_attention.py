"""Flash attention for TPU in Pallas — forward AND backward kernels.

Online-softmax blocked attention: O(seq) memory instead of the O(seq^2)
logits tensor, KV streamed through VMEM block by block. Grid is
(batch, heads, q_blocks, kv_blocks) with the kv axis innermost; running max,
denominator and the output accumulator live in VMEM scratch that persists
across the kv iterations of one q block (sequential grid execution on TPU).

The forward also emits the log-sum-exp per row; the backward is two more
blocked kernels (dq over kv blocks; dk/dv over q blocks) that recompute
P = exp(S - lse) blockwise — no O(seq^2) tensor is ever materialized in
either direction, which is what frees the HBM for larger batches at long
sequence length.

GQA reads each KV head once via the BlockSpec index map (no host-side
repeat); the dkv backward fuses (gqa rep, q block) into one grid axis so
dk/dv accumulate across the whole GQA group in VMEM — outputs are KV-head
shaped with no host-side group sum.

Kernel design follows the public flash-attention-on-TPU recipe (see
/opt/skills/guides/pallas_guide.md patterns; reference framework has no TPU
attention kernels at all — SURVEY.md §2c "Ring attention: no").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _masked_scores(q, k, qi, kj, *, scale, causal, block_q, block_k):
    """scale * Q K^T with the causal block mask — THE score definition,
    shared by the forward and both backward kernels so mask/scale changes
    (sliding window, soft-cap, ...) can never diverge between them."""
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        q_pos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
    return s


def _fwd_kernel(
    q_ref,      # [1, 1, bq, d]
    k_ref,      # [1, 1, bk, d]
    v_ref,      # [1, 1, bk, d]
    o_ref,      # [1, 1, bq, d]
    *rest,      # [lse_ref] (training only) + m/l/acc scratch
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    with_lse: bool,
):
    # lse_ref: [1, 1, bq, 8] f32 log-sum-exp, lane-broadcast (Mosaic needs
    # the last two block dims tiled; 8 lanes is the cheapest legal layout
    # for a per-row scalar). Only emitted when the backward will need it —
    # inference calls skip the extra HBM stream entirely.
    if with_lse:
        lse_ref, m_scratch, l_scratch, acc_scratch = rest
    else:
        (m_scratch, l_scratch, acc_scratch), lse_ref = rest, None
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # causal: process only kv blocks whose start is <= this q block's end
    should_run = True
    if causal:
        should_run = kj * block_k <= qi * block_q + (block_q - 1)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]
        s = _masked_scores(q, k, qi, kj, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)  # [bq, bk]

        m_prev = m_scratch[:, :1]                       # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # exp(-inf - -inf) guard: rows with no valid keys yet stay at 0
        p = jnp.exp(s - m_new)                          # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                  # [bq, 1]
        l_new = l_scratch[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)

        acc = acc_scratch[:] * corr
        acc = acc + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[:] = acc
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scratch[:, :1]
        # guard fully-masked rows (shouldn't occur with causal diag present)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            m = m_scratch[:, :1]
            lse = jnp.where(l == 0.0, NEG_INF,
                            m + jnp.log(l_safe))   # [bq, 1]
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
               with_lse):
    b, h, sq, d = q.shape
    hk = k.shape[1]
    skv = k.shape[2]
    n_rep = h // hk
    grid = (b, h, sq // block_q, skv // block_k)

    out_specs = [pl.BlockSpec((1, 1, block_q, d),
                              lambda b_, h_, i, j: (b_, h_, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, 8),
                                      lambda b_, h_, i, j: (b_, h_, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 8), jnp.float32))

    res = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, with_lse=with_lse,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


# ---------------------------------------------------------------------------
# Backward kernels. Standard flash gradient identities, recomputed blockwise
# from the saved lse (P never materialized globally):
#   S = scale * Q K^T (masked), P = exp(S - lse)
#   delta_i = sum_d dO_id * O_id
#   dV = P^T dO
#   dS = P * (dO V^T - delta)
#   dQ = scale * dS K ;  dK = scale * dS^T Q
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref,         # [1,1,bq,d] / [1,1,bk,d] x2 / [1,1,bq,d]
    lse_ref, delta_ref,                  # [1,1,bq,8] f32 (lane-broadcast)
    dq_ref,                              # [1,1,bq,d]
    dq_scratch,                          # [bq,d] f32
    *, scale, causal, block_q, block_k,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    should_run = True
    if causal:
        should_run = kj * block_k <= qi * block_q + (block_q - 1)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]         # [bq, 1]
        delta = delta_ref[0, 0][:, :1]     # [bq, 1]

        s = _masked_scores(q, k, qi, kj, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)               # masked/-inf rows -> 0
        dov = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [bq,bk]
        ds = p * (dov - delta) * scale
        dq_scratch[:] = dq_scratch[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref,         # q/do: [1,1,bq,d]; k/v: [1,1,bk,d]
    lse_ref, delta_ref,                  # [1,1,bq,8] f32 (lane-broadcast)
    dk_ref, dv_ref,                      # [1,1,bk,d] (per KV head)
    dk_scratch, dv_scratch,              # [bk,d] f32
    *, scale, causal, block_q, block_k, n_q_blocks,
):
    # inner grid axis t fuses (gqa rep, q block): rep = t // n_q_blocks,
    # qi = t % n_q_blocks — so ALL q-heads of one kv head revisit the same
    # dk/dv output block consecutively and accumulate in scratch (no
    # per-q-head HBM buffers, no host-side group sum)
    kj = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)
    qi = t % n_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    should_run = True
    if causal:
        # q block contributes iff its END reaches this kv block's start
        should_run = qi * block_q + (block_q - 1) >= kj * block_k

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = _masked_scores(q, k, qi, kj, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)
        p = jnp.exp(s - lse)                                    # [bq,bk]
        # dV += P^T dO
        dv_scratch[:] = dv_scratch[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dov = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        ds = p * (dov - delta) * scale                          # [bq,bk]
        # dK += dS^T Q
        dk_scratch[:] = dk_scratch[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, *, scale, causal, block_q, block_k,
               interpret):
    b, h, sq, d = q.shape
    hk = k.shape[1]
    skv = k.shape[2]
    n_rep = h // hk
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # [b,h,sq]
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 8))

    qd_spec = pl.BlockSpec((1, 1, block_q, d),
                           lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 8),
                            lambda b_, h_, i, j: (b_, h_, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, sq // block_q, skv // block_k),
        in_specs=[qd_spec, kv_spec, kv_spec, qd_spec, row_spec, row_spec],
        out_specs=qd_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse, delta)

    # kv-head-major grid; inner axis fuses (gqa rep, q block) so dk/dv
    # accumulate across the whole GQA group in VMEM scratch
    nq = sq // block_q
    qd_spec2 = pl.BlockSpec(
        (1, 1, block_q, d),
        lambda b_, hk_, j, t: (b_, hk_ * n_rep + t // nq, t % nq, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, hk_, j, t: (b_, hk_, j, 0))
    row_spec2 = pl.BlockSpec(
        (1, 1, block_q, 8),
        lambda b_, hk_, j, t: (b_, hk_ * n_rep + t // nq, t % nq, 0))
    dkv_spec = pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, hk_, j, t: (b_, hk_, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q_blocks=nq),
        grid=(b, hk, skv // block_k, n_rep * nq),
        in_specs=[qd_spec2, kv_spec2, kv_spec2, qd_spec2, row_spec2,
                  row_spec2],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, hk, skv, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hk, skv, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q_k, interpret):
    block_q, block_k = block_q_k
    out, _ = _flash_fwd(q, k, v, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret, with_lse=False)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q_k, interpret):
    block_q, block_k = block_q_k
    out, lse = _flash_fwd(q, k, v, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, with_lse=True)
    # named checkpoint targets: under jax.checkpoint with the
    # "attn"/"dots_attn" policies (models/llama.py) these residuals are
    # SAVED, so the backward never re-runs this kernel — the O(seq^2)
    # forward otherwise recomputes inside every remat backward, the
    # round-3 long-context MFU gap
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, block_q_k, interpret, res, g):
    q, k, v, out, lse = res
    block_q, block_k = block_q_k
    return _flash_bwd(q, k, v, out, lse, g, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _fit_block(seq: int, want: int) -> int:
    """Largest block <= ``want`` that divides ``seq``: a 128-multiple when
    the length allows, else the whole sequence as a single block (the only
    layout Mosaic accepts for odd lengths)."""
    blk = min(want, seq)
    if seq % 128 == 0 and blk >= 128:
        blk -= blk % 128
        while seq % blk:
            blk -= 128
        return blk
    while seq % blk:
        blk -= 1
    if blk < seq and seq % 128:
        raise ValueError(
            f"sequence length {seq} must be a multiple of 128, or "
            f"block_q/block_k must cover the whole sequence (>= {seq})")
    return blk


def flash_attention(
    q, k, v, *, causal: bool = True, scale: float | None = None,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool = False,
):
    # default blocks from v5e FULL-gradient in-graph sweeps (d=128,
    # fwd + dq + dk/dv kernels): (512,1024) wins at s=2048/b=8
    # (16.3ms vs 19.6 for bq=1024); at s=16k/b=1 the larger q block
    # wins ((1024,1024): 39.8ms vs 43.3) — more rows per grid step
    # amortize scratch when many kv blocks stream per q block
    """Flash attention. q/k/v: [batch, seq, heads, head_dim] (same layout as
    ``reference_attention``); returns [batch, seq, heads, head_dim].
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if block_q is None:
        block_q = 1024 if sq >= 8192 else 512
    if block_k is None:
        block_k = 1024
    scale = scale if scale is not None else d ** -0.5
    block_q = _fit_block(sq, block_q)
    block_k = _fit_block(skv, block_k)
    # kernel layout: [b, h, s, d]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, scale, causal, (block_q, block_k), interpret)
    return out.transpose(0, 2, 1, 3)
