"""Ring attention: exact attention over sequence-sharded inputs.

Net-new capability vs. the reference (SURVEY.md §2c: sequence/context
parallelism and ring attention are ABSENT there — verified by repo grep).
Design: the sequence axis is sharded over the ``sp`` mesh axis; each device
holds a contiguous [b, s/n, h, d] chunk of q/k/v. KV chunks rotate around the
ICI ring via ``lax.ppermute`` while every device accumulates blockwise
attention for its local queries with an online log-sum-exp merge — O(s/n)
memory per device, full-sequence exactness, and the KV transfer overlaps the
attention compute of the previous step (XLA schedules the ppermute
asynchronously with the matmuls).

Causality over the ring: with contiguous layout, a KV chunk that originated
on source device ``src`` relative to my index ``idx``:
    src <  idx  → all keys precede all my queries → full (unmasked) block
    src == idx  → the diagonal block → causal mask
    src >  idx  → all keys follow my queries → skipped (no compute)
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _chunk_attention(q, k, v, *, scale, mask):
    """Blockwise attention returning (o_unnormalized_by_softmax_merge, lse).

    q: [b, sq, h, d]; k/v: [b, sk, hk, d] (GQA repeat applied here).
    Returns o: [b, sq, h, d] (already divided by this block's denominator)
    and lse: [b, sq, h] log-sum-exp of this block's logits.
    """
    b, sq, h, d = q.shape
    hk = k.shape[2]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)           # [b, h, sq]
    probs = jnp.exp(logits - lse[..., None])
    probs = jnp.where(jnp.isfinite(lse)[..., None], probs, 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(jnp.float32), lse.transpose(0, 2, 1)  # lse: [b, sq, h]


def _merge(o1, lse1, o2, lse2):
    """Merge two partial attention results (log-sum-exp weighted)."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    w1 = jnp.where(jnp.isfinite(lse1)[..., None], w1, 0.0)
    w2 = jnp.where(jnp.isfinite(lse2)[..., None], w2, 0.0)
    return o1 * w1 + o2 * w2, lse


def _ring_body(axis_name: str, n: int, scale: float, causal: bool,
               q, k0, v0):
    """Per-device ring loop. q/k0/v0: local chunks [b, sc, h|hk, d]."""
    idx = lax.axis_index(axis_name)
    b, sc, h, d = q.shape

    perm = [(i, (i + 1) % n) for i in range(n)]  # send to next rank

    def step(carry, r):
        o, lse, k, v = carry
        src = (idx - r) % n  # originating device of the current kv chunk

        def attend(_):
            if causal:
                qpos = jnp.arange(sc)[:, None]
                kpos = jnp.arange(sc)[None, :]
                diag_mask = (kpos <= qpos)[None, None]
                mask = jnp.where(src == idx, diag_mask,
                                 jnp.ones_like(diag_mask))
                mask = mask & (src <= idx)
            else:
                mask = None
            return _chunk_attention(q, k, v, scale=scale, mask=mask)

        def skip(_):
            return (jnp.zeros((b, sc, h, d), jnp.float32),
                    jnp.full((b, sc, h), -jnp.inf, jnp.float32))

        if causal:
            o_r, lse_r = lax.cond(src <= idx, attend, skip, None)
        else:
            o_r, lse_r = attend(None)
        o, lse = _merge(o, lse, o_r, lse_r)
        # rotate kv to the next device (overlaps with next step's compute)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (o, lse, k, v), None

    o0 = jnp.zeros((b, sc, h, d), jnp.float32)
    lse0 = jnp.full((b, sc, h), -jnp.inf, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k0, v0), jnp.arange(n))
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused Pallas ring: per-step flash kernels for BOTH directions.
#
# Forward: each ring step runs the flash forward kernel (with LSE out) on
# the resident KV chunk; partial results merge online exactly like the
# reference-math path. Backward is a custom VJP implementing the ring
# itself: the flash backward kernels recompute P from the FINAL merged
# lse (the blockwise-global form — no per-step dlse term exists), dq
# accumulates locally, and (k, v, dk, dv) travel the ring together so a
# chunk's grads come home after n hops. Memory stays O(s/n) per device;
# every matmul is an MXU-tiled Pallas block.
# ---------------------------------------------------------------------------


def _ring_flash_steps(qt, k0, v0, axis_name, n, scale, causal, blocks,
                      interpret):
    """Forward ring in kernel layout [b, h, s, d]; returns (o f32, lse
    f32 [b,h,s])."""
    from ray_tpu.ops.flash_attention import _fit_block, _flash_fwd

    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    sc = qt.shape[2]
    bq = _fit_block(sc, blocks[0])
    bk = _fit_block(sc, blocks[1])

    # r = 0: the diagonal chunk — STATICALLY causal (kernel-level mask)
    o, lse8 = _flash_fwd(qt, k0, v0, scale=scale, causal=causal,
                         block_q=bq, block_k=bk, interpret=interpret,
                         with_lse=True)
    o = o.astype(jnp.float32)
    lse = lse8[..., 0]
    k = lax.ppermute(k0, axis_name, perm)
    v = lax.ppermute(v0, axis_name, perm)

    def step(carry, r):
        o, lse, k, v = carry

        def attend(_):
            o_r, lse_r = _flash_fwd(qt, k, v, scale=scale, causal=False,
                                    block_q=bq, block_k=bk,
                                    interpret=interpret, with_lse=True)
            return o_r.astype(jnp.float32), lse_r[..., 0]

        def skip(_):
            return (jnp.zeros_like(o),
                    jnp.full_like(lse, -jnp.inf))

        if causal:
            # chunk from src=(idx-r)%n precedes my queries iff idx >= r
            o_r, lse_r = lax.cond(idx >= r, attend, skip, None)
        else:
            o_r, lse_r = attend(None)
        o, lse = _merge(o, lse, o_r, lse_r)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (o, lse, k, v), None

    if n > 1:
        (o, lse, _, _), _ = lax.scan(step, (o, lse, k, v),
                                     jnp.arange(1, n))
    return o, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(qt, k0, v0, axis_name, n, scale, causal, blocks,
                interpret):
    o, _ = _ring_flash_steps(qt, k0, v0, axis_name, n, scale, causal,
                             blocks, interpret)
    return o.astype(qt.dtype)


def _ring_flash_vjp_fwd(qt, k0, v0, axis_name, n, scale, causal, blocks,
                        interpret):
    o, lse = _ring_flash_steps(qt, k0, v0, axis_name, n, scale, causal,
                               blocks, interpret)
    o = o.astype(qt.dtype)
    return o, (qt, k0, v0, o, lse)


def _ring_flash_vjp_bwd(axis_name, n, scale, causal, blocks, interpret,
                        res, do):
    from ray_tpu.ops.flash_attention import _fit_block, _flash_bwd

    qt, k0, v0, o, lse = res
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    sc = qt.shape[2]
    bq = _fit_block(sc, blocks[0])
    bk = _fit_block(sc, blocks[1])
    lse8 = jnp.broadcast_to(lse[..., None], (*lse.shape, 8))
    do = do.astype(qt.dtype)

    # r = 0: own (diagonal) chunk, statically causal kernels
    dq_acc, dk, dv = _flash_bwd(qt, k0, v0, o, lse8, do, scale=scale,
                                causal=causal, block_q=bq, block_k=bk,
                                interpret=interpret)
    dq_acc = dq_acc.astype(jnp.float32)
    # (k, v, dk, dv) ride the ring together: after n hops each chunk's
    # accumulated grads are home
    k = lax.ppermute(k0, axis_name, perm)
    v = lax.ppermute(v0, axis_name, perm)
    dk = lax.ppermute(dk.astype(jnp.float32), axis_name, perm)
    dv = lax.ppermute(dv.astype(jnp.float32), axis_name, perm)

    def step(carry, r):
        dq_acc, k, v, dk, dv = carry

        def compute(_):
            dq_r, dk_r, dv_r = _flash_bwd(
                qt, k, v, o, lse8, do, scale=scale, causal=False,
                block_q=bq, block_k=bk, interpret=interpret)
            return (dq_r.astype(jnp.float32), dk_r.astype(jnp.float32),
                    dv_r.astype(jnp.float32))

        def skip(_):
            return (jnp.zeros_like(dq_acc), jnp.zeros_like(dk),
                    jnp.zeros_like(dv))

        if causal:
            dq_r, dk_r, dv_r = lax.cond(idx >= r, compute, skip, None)
        else:
            dq_r, dk_r, dv_r = compute(None)
        dq_acc = dq_acc + dq_r
        dk = lax.ppermute(dk + dk_r, axis_name, perm)
        dv = lax.ppermute(dv + dv_r, axis_name, perm)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (dq_acc, k, v, dk, dv), None

    if n > 1:
        (dq_acc, _, _, dk, dv), _ = lax.scan(
            step, (dq_acc, k, v, dk, dv), jnp.arange(1, n))
    return (dq_acc.astype(qt.dtype), dk.astype(k0.dtype),
            dv.astype(v0.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def _ring_flash_body(axis_name, n, scale, causal, blocks, interpret,
                     q, k0, v0):
    """shard_map body adapter: [b, sc, h, d] boundary layout <-> the
    kernels' [b, h, s, d]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k0.transpose(0, 2, 1, 3)
    vt = v0.transpose(0, 2, 1, 3)
    out = _ring_flash(qt, kt, vt, axis_name, n, scale, causal, blocks,
                      interpret)
    return out.transpose(0, 2, 1, 3)


def batch_head_spec(mesh: Mesh, b: int, h: int, hk: int, *,
                    batch_axes=("dp", "fsdp"), head_axis: str = "tp",
                    seq_axis: str | None = None) -> P:
    """PartitionSpec of a [batch, seq, heads, head_dim] attention operand
    inside a ``shard_map`` over ``mesh``: batch over ``batch_axes`` and
    heads over ``head_axis``, each only where the axis is on the mesh
    with size > 1 and divides the dim (``hk`` kv heads too), so the
    region does not replicate compute across mesh axes that partition
    independent work. ``seq_axis`` shards the sequence (ring attention)."""
    b_ax = tuple(
        a for a in batch_axes
        if a in mesh.axis_names and mesh.shape[a] > 1
    )
    if b_ax and b % math.prod(mesh.shape[a] for a in b_ax):
        b_ax = ()
    h_ax = (
        head_axis
        if head_axis in mesh.axis_names and mesh.shape[head_axis] > 1
        and h % mesh.shape[head_axis] == 0 and hk % mesh.shape[head_axis] == 0
        else None
    )
    return P(b_ax or None, seq_axis, h_ax, None)


def ring_attention(
    q, k, v, *, mesh: Mesh, axis: str = "sp", causal: bool = True,
    scale: float | None = None, batch_axes=("dp", "fsdp"),
    head_axis: str = "tp", impl: str = "auto",
    block_q: int = 512, block_k: int = 1024,
):
    """Exact attention with the sequence axis sharded over ``axis``.

    q/k/v: [batch, seq, heads, head_dim] GLOBAL arrays (sharded or not —
    shard_map re-shards per in_specs). Returns same-shape output sharded the
    same way. Callable inside jit.

    The batch dim stays sharded over ``batch_axes`` and heads over
    ``head_axis`` (when present on the mesh and divisible) so the shard_map
    region does NOT replicate compute across non-sp mesh axes — the ring
    only rotates along ``axis``; all other axes partition independent work.
    """
    if mesh is None:
        raise ValueError("ring_attention requires mesh=")
    b, s, h, d = q.shape
    hk = k.shape[2]
    n = mesh.shape[axis]
    if s % n:
        raise ValueError(f"seq {s} not divisible by {axis} size {n}")
    scale = scale if scale is not None else d ** -0.5

    spec = batch_head_spec(mesh, b, h, hk, batch_axes=batch_axes,
                           head_axis=head_axis, seq_axis=axis)

    if impl not in ("auto", "flash", "reference"):
        raise ValueError(
            f"ring_attention impl must be 'auto', 'flash' or 'reference', "
            f"got {impl!r}")
    use_flash = impl == "flash" or (
        impl == "auto" and jax.devices()[0].platform == "tpu")
    if use_flash:
        # interpret-mode keeps the fused path testable off-TPU
        interpret = jax.devices()[0].platform != "tpu"
        body = partial(_ring_flash_body, axis, n, scale, causal,
                       (block_q, block_k), interpret)
    else:
        body = partial(_ring_body, axis, n, scale, causal)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
