"""Time one latent layer's prefill attention at ``serve-note-gen``'s shapes.

The cold transcript (one row of 4,096 query rows, 3,600 of them valid,
over a 32-page table) and the suffix programs behind a cached transcript
(64, 128 and 256 query rows from position 3,700 or so) of a full layer
(128 heads, 128 + 64 | 128 wide, a latent of 512, 64 indexer heads that
keep 2,048 keys) and of a sliding one (64 heads, 192 + 64 | 128, a latent
of 1,024, a window of 513): the plain formulation against the kernel's,
each from the rows' pool to the heads' outputs (gather, expansion and, in
a full layer, index scores and ``kept`` included), with how far the two
disagree on the chip over the valid queries; beside them the kernel's
launch alone (expanded keys and flags given) and the selection's flags
alone. ``--exact`` adds how far EACH is from the same softmax over the
same keys in float64 on the host (the same bf16 queries, expanded keys
and values, ``kept``'s set): which of the two is nearer the truth
(numpy's sums, a head at a time: a quarter of an hour a cold layer on
the chip's host, so ``--only cold`` with it). PERF.md's tables of PR 58 are this script's output. Run on the
chip:

    python scripts/sweep_latent_prefill.py [--toy]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import latent_attention as la  # noqa: E402

# heads, no-position width, latent rank, lanes of a row, window, indexer
# heads (0: none)
LAYERS = {"full": (128, 128, 512, 640, None, 64),
          "sliding": (64, 192, 1024, 1152, 513, 0)}
ROPE, VALUE, INDEX_DIM, TOPK, PAGE, POOL = 64, 128, 128, 2048, 128, 600
# query rows, valid of them, the first's position, table pages
PROGRAMS = {"cold-1x4096": (4096, 3600, 0, 32),
            "check-1x2048": (2048, 1952, 2048, 32),
            "suffix-1x256": (256, 200, 3712, 32),
            "suffix-1x128": (128, 100, 3712, 32),
            "suffix-1x64": (64, 50, 3712, 32)}


def _time(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _exact(q, k, v, flags, start, valid, scale, window):
    """[valid, H, dv] float64: the softmax of row 0's valid queries ``q``
    [1, H, T, dk] over the keys each may see (causal from ``start``,
    ``window``, ``flags`` [1, T, S]), a head at a time."""
    def f64(a):
        return np.asarray(a.astype(jnp.float32), np.float64)
    q, k, v = f64(q[0, :, :valid]), f64(k[0]), f64(v[0])
    qpos = start + np.arange(valid)[:, None]
    kpos = np.arange(k.shape[1])[None]
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    if flags is not None:
        seen &= np.asarray(flags[0, :valid]) != 0
    out = np.empty((valid, q.shape[0], v.shape[-1]))
    for h in range(q.shape[0]):
        s = np.where(seen, q[h] @ k[h].T * scale, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[h]
    return out


def sweep(layer, program, iters, toy, exact=False):
    heads, dn, rank, lanes, window, index_heads = LAYERS[layer]
    t, valid, start, pages = PROGRAMS[program]
    page, pool_pages, topk = PAGE, POOL, TOPK
    if toy:
        heads, index_heads = heads // 16, index_heads // 16
        t, valid, start, page = t // 16, valid // 16, start // 16, page // 16
        topk, window = topk // 16, window and window // 16
    keys = jax.random.split(jax.random.key(0), 8)
    dt = jnp.bfloat16

    def normal(key, shape, scale=1.0, dtype=dt):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    pool = normal(keys[0], (2, pool_pages, page, lanes)).at[
        ..., rank + ROPE:].set(0)
    pools = (pool,)
    index = None
    if index_heads:
        pools += (normal(keys[1], (2, pool_pages, page, INDEX_DIM)),)
        index = la.IndexInputs(
            normal(keys[2], (1, t, index_heads, INDEX_DIM)),
            normal(keys[3], (1, t, index_heads), dtype=jnp.float32),
            None, topk)
    q = normal(keys[4], (1, t, heads, dn + ROPE), 0.5)
    wkv_b = normal(keys[5], (rank, heads, dn + VALUE), rank ** -0.5)
    scale = (dn + ROPE) ** -0.5
    table = jnp.asarray(np.random.default_rng(0).permutation(pool_pages)[
        :pages].reshape(1, pages), jnp.int32)
    starts = jnp.full((1,), start, jnp.int32)
    slens = jnp.full((1,), valid, jnp.int32)
    selects = index is not None and pages * page > topk
    if toy:
        la.latent_prefill_attention_kernel = functools.partial(
            la.latent_prefill_attention_kernel, interpret=True)
    in_kernel, plain, _ = la._prefill_formulations(
        scale, window, topk if selects else None, ROPE)
    args = (q, wkv_b,
            (index.q, index.weights) if selects else None, pools,
            jnp.int32(1), table, starts, slens)
    out = {"layer": layer, "program": program,
           "engages": la.latent_prefill_kernel_engages(
               q.shape, pool, pages, window,
               index_heads if selects else 0),
           "plain_block": la.query_block(
               1, t, heads + (index_heads if selects else 0), pages * page,
               window)}
    calls = {"plain_ms": jax.jit(plain), "kernel_path_ms": jax.jit(in_kernel)}
    out.update({what: _time(fn, args, iters) for what, fn in calls.items()})
    got, want = (np.asarray(calls[c](*args), np.float32)[:, :valid]
                 for c in ("kernel_path_ms", "plain_ms"))
    out["max_abs_diff"] = float(np.abs(got - want).max())
    out["rms_diff"] = float(np.sqrt(np.mean((got - want) ** 2)))
    out["rms_out"] = float(np.sqrt(np.mean(want ** 2)))

    # the kernel's launch alone, and the selection's flags alone
    rows = la.gather_rows(pool, jnp.int32(1), table)
    kr, kn, v = la._expanded(q, wkv_b, rows, ROPE)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, None], (1, heads, *kr.shape[1:]))], -1)
    flags = None
    if selects:
        index_keys = la.gather_rows(pools[1], jnp.int32(1), table)
        select = jax.jit(functools.partial(la.selection_flags, topk=topk))
        out["flags_ms"] = _time(
            select, ((index.q, index.weights), index_keys, starts), iters)
        flags = select((index.q, index.weights), index_keys, starts)
    launch = jax.jit(functools.partial(
        la.latent_prefill_attention_kernel, scale=scale,
        window=window))
    out["kernel_ms"] = _time(
        launch, (jnp.moveaxis(q, 1, 2), k, v, starts, slens, flags),
        iters)
    if exact:
        truth = _exact(jnp.moveaxis(q, 1, 2), k, v, flags, start, valid,
                       scale, window)
        for name, a in (("plain", want), ("kernel", got)):
            out[f"rms_{name}_exact"] = float(
                np.sqrt(np.mean((a[0] - truth) ** 2)))
            out[f"max_{name}_exact"] = float(np.abs(a[0] - truth).max())
    out["expand_ms"] = _time(
        jax.jit(lambda pool, w: la._expanded(
            q, w, la.gather_rows(pool, jnp.int32(1), table), ROPE)),
        (pool, wkv_b), iters)
    # query-key pairs a head that the softmax may run over, and the
    # matmuls' share of the chip's 197 TFLOP/s that the kernel's time is
    pairs = sum(min(start + i + 1, window or (1 << 30))
                for i in range(valid))
    out["roofline_pct"] = (2.0 * pairs * heads * (dn + ROPE + VALUE) / 197e12
                           / (out["kernel_ms"] * 1e-3) * 100)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true",
                    help="a sixteenth of every length, the kernel in "
                         "interpret mode: the CPU's rehearsal")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--only", default="")
    ap.add_argument("--exact", action="store_true")
    ap.add_argument("--blocks", default="",
                    help="queries a block, keys a chunk, heads a step of "
                         "the kernel's walk, as q,k,h: the sweep that chose "
                         "512,512,4")
    ns = ap.parse_args()
    if ns.blocks:
        (la._PREFILL_BLOCK_Q, la._PREFILL_CHUNK,
         la._PREFILL_HEADS) = map(int, ns.blocks.split(","))
    print(json.dumps({"device": str(jax.devices()[0]),
                      "blocks": [la._PREFILL_BLOCK_Q, la._PREFILL_CHUNK,
                                 la._PREFILL_HEADS]}))
    for layer in LAYERS:
        for program in PROGRAMS:
            if ns.only and ns.only not in f"{layer}-{program}":
                continue
            print(json.dumps(sweep(layer, program,
                                   1 if ns.toy else ns.iters, ns.toy,
                                   ns.exact)),
                  flush=True)


if __name__ == "__main__":
    main()
