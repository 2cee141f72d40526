"""Attention ops: GQA/MHA causal attention with fp32 softmax.

The default implementation is pure-XLA einsum attention — on TPU, XLA fuses
the QK^T → softmax → PV chain reasonably well at small/medium sequence
lengths. The Pallas flash kernel (``ray_tpu.ops.flash_attention``) replaces it
on TPU for long sequences; ``attention()`` dispatches.

Conventions: q/k/v are [batch, seq, heads, head_dim]; GQA is expressed by
n_kv_heads < n_heads with n_heads % n_kv_heads == 0.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from einops import rearrange

from ray_tpu.ops import scopes


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def reference_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    segment_ids=None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
):
    """Einsum attention with fp32 logits/softmax.

    ``segment_ids`` ([batch, seq], int) masks cross-segment attention —
    used for sequence packing.
    """
    b, sq, nh, hd = q.shape
    _, skv, nkv, _ = k.shape
    n_rep = nh // nkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = scale if scale is not None else hd ** -0.5

    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

    mask = None
    if causal:
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(skv)[None, :]
        # allow decode: query block sits at the END of the kv window
        mask = kpos <= qpos + (skv - sq)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        seg_mask = seg_mask[:, None, :, :]  # [b, 1, q, k]
        mask = seg_mask if mask is None else (mask[None, None] & seg_mask)
    elif mask is not None:
        mask = mask[None, None]

    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def cached_attention(q, k_cache, v_cache, start, *, scale, window=None,
                     key_start=None, seen=None):
    """Plain attention of new queries over a cache that already holds
    their keys. q: [B, T, nh, hd]; caches [B, S, nkv, hd]; start [B] =
    offset of the first query token. Causal over the whole cache: query i
    attends to key positions <= start + i, and with ``window`` to those
    > start + i - window alone (a sliding layer). ``key_start`` [B] is the
    position of the cache's first row where that is not 0 (a caller that
    hands a windowed layer only the rows its queries can see). ``seen``
    [B, T, S] bool: of those keys, the ones a query's softmax runs over
    (a layer that picks its keys: ``ops/index_select.py``). The
    library's plain decode (``models/decoding.py``), the serving engine's
    prefill and the non-TPU lowering of its decode attention all call
    this."""
    b, t, nh, hd = q.shape
    s = k_cache.shape[1]
    nkv = k_cache.shape[2]
    n_rep = nh // nkv
    # Grouped attention without materializing repeated KV: fold the
    # query heads as [B, T, nkv, n_rep, hd] and contract against the
    # cache directly — repeating K/V would multiply HBM traffic on the
    # hottest decode-step tensor by n_rep.
    with jax.named_scope(scopes.ATTN):
        qg = q.reshape(b, t, nkv, n_rep, hd)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
        qpos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        kpos = jnp.arange(s, dtype=jnp.int32)                        # [S]
        if key_start is None:
            mask = kpos[None, None, :] <= qpos[:, :, None]           # [B,T,S]
        else:
            kpos = key_start[:, None] + kpos[None, :]                # [B,S]
            mask = kpos[:, None, :] <= qpos[:, :, None]
        if window is not None:
            mask = mask & (kpos[..., None, :] > qpos[:, :, None] - window)
        if seen is not None:
            mask = mask & seen
        logits = jnp.where(mask[:, None, None, :, :], logits,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        return out.reshape(b, t, nh, hd).astype(q.dtype)


def attention(q, k, v, *, causal=True, segment_ids=None,
              logits_soft_cap=None, impl: str = "auto", mesh=None):
    """Dispatching entry point. ``impl``: auto | reference | flash.

    ``mesh`` is the mesh the caller's arrays are sharded over, if any:
    the compiler cannot partition a Pallas kernel by itself, so on more
    than one device the flash kernel runs inside ``shard_map``."""
    if impl == "auto":
        impl = "flash" if _flash_supported(q, segment_ids, logits_soft_cap, causal) else "reference"
    if impl == "flash":
        if segment_ids is not None or logits_soft_cap is not None:
            raise ValueError(
                "impl='flash' does not support segment_ids/logits_soft_cap "
                "yet; use impl='reference'"
            )
        from ray_tpu.ops.flash_attention import flash_attention

        kernel = partial(flash_attention, causal=causal)
        if mesh is not None and mesh.size > 1:
            from ray_tpu.parallel.ring_attention import batch_head_spec

            # attention is independent per example and per head: each
            # device runs the kernel on its share of both
            spec = batch_head_spec(mesh, q.shape[0], q.shape[2], k.shape[2])
            kernel = jax.shard_map(kernel, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=False)
        with jax.named_scope(scopes.ATTN):
            return kernel(q, k, v)
    from jax.ad_checkpoint import checkpoint_name

    # save point for the "attn"/"dots_attn" remat policies (the flash
    # impl names its kernel residuals instead — _flash_vjp_fwd)
    with jax.named_scope(scopes.ATTN):
        return checkpoint_name(
            reference_attention(
                q, k, v, causal=causal, segment_ids=segment_ids,
                logits_soft_cap=logits_soft_cap,
            ),
            "attn_out")


def _flash_supported(q, segment_ids, logits_soft_cap, causal) -> bool:
    if segment_ids is not None or logits_soft_cap is not None or not causal:
        return False
    # works under tracing: dispatch on the process-level default backend
    if jax.default_backend() != "tpu":
        return False
    # flash kernel block constraints
    b, s, h, d = q.shape
    return s >= 256 and s % 128 == 0 and d in (64, 128, 256)
