"""Time a decode step's index scores at ``serve-note-gen``'s shapes.

One full layer's scores of 64 slots of 3.0-5.1k keys (64 index heads of
128, a 64-page table of 128-key pages over a stacked pool of 2,816 pages):
the plain formulation (``_scored_gathered``: the whole table's pages
copied out, ``index_scores`` over the copy) against the index kernel at
several pages a step of its walk, with how far the two disagree on the
chip, and the latent kernel beside them (the two share ``_walk``). The
table over ``_INDEX_GROUP`` in ``ops/latent_attention.py`` and PERF.md
section 5 is this script's output. Run on the chip:

    python scripts/sweep_index_kernel.py [--groups 8,16,32] [--toy]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import index_select
from ray_tpu.ops import latent_attention as la

# slots, index heads, key width, page, table pages, pool pages, layers,
# a slot's keys (low, high), topk, latent heads, rank, lanes
CELL = (64, 64, 128, 128, 64, 2816, 2, (3000, 5100), 2048, 128, 512, 640)
TOY = (4, 4, 128, 128, 8, 40, 2, (300, 900), 256, 4, 128, 256)


def _time(step, carry, held, iters):
    """Seconds a call of ``step`` (carry, held -> carry), ``iters`` calls
    chained through their results inside one program (``held``: the
    arrays a call reads, arguments and not constants of the program)."""
    run = jax.jit(lambda c, held: jax.lax.scan(
        lambda c, _: (step(c, held), None), c, None, length=iters)[0])
    jax.block_until_ready(run(carry, held))
    t0 = time.perf_counter()
    jax.block_until_ready(run(carry, held))
    return (time.perf_counter() - t0) / iters


def _chained(scores_of):
    """``q, held -> q`` through a call's scores (a sum that weighs
    nothing)."""
    def step(q, held):
        live = jnp.where(scores_of(q, held) > la.MASKED, 1.0, 0.0)
        return q + (live.sum() * 0.0).astype(q.dtype)
    return step


def sweep(shape, groups, iters, interpret):
    (slots, hi, di, page, pb, pages, layers, (low, high), topk, heads, rank,
     lanes) = shape
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 5)
    pool = jax.random.normal(keys[0], (layers, pages, page, di),
                             jnp.bfloat16)
    q = jax.random.normal(keys[1], (slots, 1, hi, di), jnp.bfloat16)
    weights = jax.random.normal(keys[2], (slots, 1, hi), jnp.float32)
    count = jnp.asarray(rng.integers(low, high, slots), jnp.int32)
    table = np.full((slots, pb), -1, np.int32)
    order = rng.permutation(pages)
    at = 0
    for slot, n in enumerate(np.asarray(count)):
        held = -(-int(n) // page)
        table[slot, :held] = order[at:at + held]
        at += held
    table = jnp.asarray(table)
    layer = jnp.int32(1)
    live_keys = int(np.asarray(count).sum())
    out = {"slots": slots, "keys": live_keys,
           "floor_ms": live_keys * di * 2 / 819e9 * 1e3}

    held = (weights, pool, layer, table, count)

    def plain(q, held):
        return index_select._scored_gathered(q, *held)

    want = np.asarray(jax.jit(plain)(q, held))
    out["plain_ms"] = _time(_chained(plain), q, held, iters) * 1e3
    for group in groups:
        index_select._INDEX_GROUP = group

        def kernel(q, held):
            # (the launcher is jitted: its own function here, so that
            # each group traces anew)
            return index_select.index_decode_scores_kernel.__wrapped__(
                q[:, 0], held[0][:, 0], *held[1:], interpret=interpret)

        got = np.asarray(jax.jit(kernel)(q, held))
        seen = want > la.MASKED
        out[f"kernel_g{group}"] = {
            "ms": _time(_chained(kernel), q, held, iters) * 1e3,
            "masked_alike": bool(np.array_equal(seen, got > la.MASKED)),
            "max_rel": float((np.abs(got - want)[seen]
                              / np.maximum(np.abs(want[seen]), 1.0)).max()),
            "kept_alike": bool(np.array_equal(
                np.asarray(la.kept(jnp.asarray(got), topk)),
                np.asarray(la.kept(jnp.asarray(want), topk))))}
    # the latent kernel over the same slots (flags: the first ``topk``)
    rows = jax.random.normal(keys[3], (layers, pages, page, lanes),
                             jnp.bfloat16)
    q_row = jax.random.normal(keys[4], (slots, heads, lanes), jnp.bfloat16)
    flags = jnp.arange(pb * page)[None] < jnp.minimum(count, topk)[:, None]

    def latent(q_row, held):
        o = la.latent_decode_attention_kernel.__wrapped__(
            q_row, *held, rank=rank, scale=0.07, interpret=interpret)
        return q_row + (o.astype(jnp.float32).sum() * 0.0).astype(
            q_row.dtype)

    out["latent_kernel_ms"] = _time(
        latent, q_row, (rows, layer, table, count, flags), iters) * 1e3
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default="4,8,16,32,64")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--toy", action="store_true",
                    help="tiny shapes in interpret mode, off the chip")
    args = ap.parse_args()
    groups = [int(g) for g in args.groups.split(",")]
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("a time comes from the chip: --toy rehearses")
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        **sweep(TOY if args.toy else CELL, groups,
                2 if args.toy else args.iters, not on_tpu)}))


if __name__ == "__main__":
    main()
