"""Time ``kept`` at the serving cells' shapes, form by form.

A query's selection is the set ``lax.top_k`` returns of its index scores
(``ops/index_select.py:kept``). On this chip ``lax.top_k`` is a whole
``sort`` of every row; ``kept`` finds the ``topk``-th score by a threshold
search instead. This script times, at a decode step's scores (``[32,
8192]`` of ``serve-longqa-gen``, ``[64, 8192]`` of ``serve-note-gen``) and
a prefill block's (``[1, 2048, S]`` over the three key groups, and a warm
suffix's ``[1, 128, 8192]``), ``topk`` 2,048:

- ``top_k``: the mask from ``lax.top_k`` (what ``kept`` was);
- ``plain``: ``kept`` as it is: the search in ``jax.numpy`` (every pass a
  fused compare-and-count over the rows);
- ``kernel_b<KiB>``: the same search as the body of a Pallas kernel over
  blocks of rows of that many KiB of scores, a block on the core for all
  its passes (this script's own: faster alone, and not chosen: PERF.md,
  Findings, PR 61);
- ``lanes_r<rows>``: that kernel with each count made a chunk of 128
  lanes at a time into a ``[rows, 128]`` accumulator;

each on seeded scores that all differ (``ms``: what a trained indexer
gives; the search then makes no pass over the ties' positions) and on
scores with ties across the ``topk``-th place in every row
(``ms_with_ties``: quantised scores, zeros of both signs, a tail of
``MASKED``, rows of ``MASKED`` alone), and says, for each, whether its
mask IS ``lax.top_k``'s set on both. PERF.md section 5 holds this
script's table. Run on the chip:

    python scripts/sweep_kept.py [--iters 20] [--toy]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.ops import index_select as ix  # noqa: E402

SHAPES = ((32, 8192), (64, 8192), (1, 2048, 4096), (1, 2048, 6144),
          (1, 2048, 8192), (1, 128, 8192))
TOPK = 2048
TOY_SHAPES = ((4, 512), (1, 16, 384))
TOY_TOPK = 128


def top_k_mask(chosen, topk):
    """``lax.top_k``'s set by position, as a mask."""
    _, positions = lax.top_k(chosen, topk)
    rows = positions.reshape(-1, topk)
    mask = jnp.zeros((rows.shape[0], chosen.shape[-1]), bool)
    mask = mask.at[jnp.arange(rows.shape[0])[:, None], rows].set(True)
    return mask.reshape(chosen.shape)


def top_k_kept(chosen, topk):
    """``kept`` as it was: the ``topk``-th value and the last tie taken,
    read off ``lax.top_k``'s sorted rows."""
    values, positions = lax.top_k(chosen, topk)
    kth = values[..., -1:]
    last = jnp.max(jnp.where(values == kth, positions, -1), axis=-1,
                   keepdims=True)
    at = jnp.arange(chosen.shape[-1], dtype=positions.dtype)
    return (chosen > kth) | ((chosen == kth) & (at <= last))


def of_rows(rows: int, width: int):
    """A grid step's block of ``rows`` rows of an array [R, width]."""
    return pl.BlockSpec((rows, width), lambda r: (r, 0))


def _masked(searched):
    """``kept`` with ``searched`` ([R, S] float32 -> threshold, position,
    [R, 1] int32 each) in the search's place."""
    def form(chosen, topk):
        width = chosen.shape[-1]
        kth, last = searched(chosen.reshape(-1, width), topk=topk)
        lead = (*chosen.shape[:-1], 1)
        keys = ix._ordered(chosen)
        at = jnp.arange(width, dtype=jnp.int32)
        return ((keys > kth.reshape(lead))
                | ((keys == kth.reshape(lead)) & (at <= last.reshape(lead))))
    return form


def _block_kernel(scores_ref, kth_ref, last_ref, *, topk):
    """One grid step a block of rows: the library's search over it."""
    kth_ref[...], last_ref[...] = ix._searched(
        ix._ordered(scores_ref[...]), topk)


def _launched(kernel, scores, *, topk, block, interpret, scratch=()):
    """``kernel`` over ``scores`` [R, S] in blocks of ``block`` rows (the
    last block past ``R`` where it does not divide)."""
    rows, width = scores.shape
    block = min(block, rows)
    out = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    return pl.pallas_call(
        functools.partial(kernel, topk=topk),
        grid=(pl.cdiv(rows, block),),
        in_specs=[of_rows(block, width)],
        out_specs=(of_rows(block, 1), of_rows(block, 1)),
        out_shape=(out, out),
        scratch_shapes=[pltpu.VMEM((block, width), jnp.int32)
                        for _ in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="index_kth_search",
    )(scores)


def block_searched(scores, *, topk, kib, interpret):
    rows = max(8, (kib << 10) // (4 * scores.shape[1]) // 8 * 8)
    return _launched(_block_kernel, scores, topk=topk, block=rows,
                     interpret=interpret)


def _lanes_kernel(scores_ref, kth_ref, last_ref, keys_ref, *, topk):
    """``_searched`` with every count a chunk of 128 lanes at a time."""
    rows, width = scores_ref.shape
    keys_ref[...] = ix._ordered(scores_ref[...])

    def count(which):
        def chunk(j, acc):
            at = pl.multiple_of(j * 128, 128)
            return acc + jnp.where(
                which(keys_ref[:, pl.ds(at, 128)], at), 1, 0)
        acc = lax.fori_loop(0, width // 128, chunk,
                            jnp.zeros((rows, 128), jnp.int32), unroll=True)
        return jnp.sum(acc, axis=1, keepdims=True)

    def raised(held, bit, holds):
        higher = held | bit
        return jnp.where(holds(higher), higher, held)

    def at_least(t):
        return count(lambda keys, _: keys >= t) >= topk

    lowest = jnp.full((rows, 1), jnp.iinfo(jnp.int32).min)
    t = jnp.where(at_least(jnp.zeros_like(lowest)), 0, lowest)
    t = lax.fori_loop(
        0, 31, lambda i, t: raised(t, jnp.int32(1) << (30 - i), at_least), t)
    need = topk - count(lambda keys, _: keys > t)
    spare = count(lambda keys, _: keys == t) > need
    lane = lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    bits = max(width - 1, 1).bit_length()

    def nth():
        return lax.fori_loop(
            0, bits, lambda i, last: raised(
                last, jnp.int32(1) << (bits - 1 - i),
                lambda p: count(
                    lambda keys, at: (keys == t) & (lane + at < p)) < need),
            jnp.zeros_like(lowest))

    end = jnp.full_like(lowest, width - 1)
    last = lax.cond(jnp.max(spare.astype(jnp.int32)) > 0, nth, lambda: end)
    kth_ref[...], last_ref[...] = t, jnp.where(spare, last, end)


def lanes_searched(scores, *, topk, block, interpret):
    return _launched(_lanes_kernel, scores, topk=topk, block=block,
                     interpret=interpret, scratch=("keys",))


def scores_with_ties(shape, topk, seed):
    """Seeded index scores with what a search could get wrong: a few
    hundred distinct values (ties across the ``topk``-th place in every
    row), zeros of both signs, negatives, and per row a tail of
    ``MASKED`` from a seen count on: some rows see fewer than ``topk``
    keys, one exactly ``topk``, one none."""
    rng = np.random.default_rng(seed)
    width = shape[-1]
    rows = int(np.prod(shape[:-1]))
    x = np.round(rng.standard_normal((rows, width)) * 8).astype(np.float32)
    x = x / 8 + np.where(rng.random((rows, width)) < 0.5, 0.0,
                         rng.standard_normal((rows, width)) * 1e-3)
    zeros = rng.random((rows, width))
    x = np.where(zeros < 0.1, 0.0, np.where(zeros < 0.2, -0.0, x))
    seen = rng.integers(topk // 2, width + 1, rows)
    seen[0], seen[-1] = topk, 0
    if rows > 2:
        seen[1] = width
    x = np.where(np.arange(width)[None] < seen[:, None], x, ix.MASKED)
    return jnp.asarray(x.astype(np.float32).reshape(shape))


def scores_that_differ(shape, seed):
    """Seeded index scores as a trained indexer's are: no two of a row
    alike, every key seen."""
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _time(form, chosen, topk, iters):
    """Seconds a call of ``form``, ``iters`` calls chained through one
    score of the block inside one program."""
    def step(chosen, _):
        some = jnp.sum(form(chosen, topk)).astype(jnp.float32)
        return chosen.at[(0,) * chosen.ndim].add(some * 0.0), None

    run = jax.jit(lambda c: lax.scan(step, c, None, length=iters)[0],
                  donate_argnums=0)
    chosen = jax.block_until_ready(run(chosen + 0.0))
    t0 = time.perf_counter()
    jax.block_until_ready(run(chosen))
    return (time.perf_counter() - t0) / iters


def forms(blocks, lanes_rows, interpret):
    out = {"top_k": top_k_kept, "plain": ix.kept}
    for kib in blocks:
        out[f"kernel_b{kib}"] = _masked(functools.partial(
            block_searched, kib=kib, interpret=interpret))
    for rows in lanes_rows:
        out[f"lanes_r{rows}"] = _masked(functools.partial(
            lanes_searched, block=rows, interpret=interpret))
    return out


def sweep(shapes, topk, blocks, lanes_rows, iters, interpret):
    table = {}
    for shape in shapes:
        chosen = scores_with_ties(shape, topk, seed=shape[-1] + shape[-2])
        plain = scores_that_differ(shape, seed=shape[-1] + shape[-2])
        want, want_plain = (
            np.asarray(jax.jit(functools.partial(top_k_mask, topk=topk))(x))
            for x in (chosen, plain))
        row = {}
        for name, form in forms(blocks, lanes_rows, interpret).items():
            try:
                masked = jax.jit(functools.partial(form, topk=topk))
                off = sum(int((np.asarray(masked(x)) != w).sum())
                          for x, w in ((chosen, want), (plain, want_plain)))
                row[name] = {
                    "ms": _time(form, plain, topk, iters) * 1e3,
                    "ms_with_ties": _time(form, chosen, topk, iters) * 1e3,
                    "is_top_ks_set": off == 0, "keys_off": off}
            except Exception as e:   # a form the compiler refuses: say so
                row[name] = {"refused": f"{type(e).__name__}: {e}"[:300]}
        table["x".join(map(str, shape))] = row
        print(json.dumps({"shape": shape, **row}), flush=True)
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="256,512,1024,2048,4096",
                    help="the kernel's block of rows, KiB of scores")
    ap.add_argument("--lanes-rows", default="8,32")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--toy", action="store_true",
                    help="tiny shapes in interpret mode, off the chip")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("a time comes from the chip: --toy rehearses")
    blocks = [int(b) for b in args.blocks.split(",") if b]
    lanes_rows = [int(r) for r in args.lanes_rows.split(",") if r]
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "topk": TOY_TOPK if args.toy
        else TOPK, "table": sweep(
            TOY_SHAPES if args.toy else SHAPES,
            TOY_TOPK if args.toy else TOPK, blocks, lanes_rows,
            2 if args.toy else args.iters, not on_tpu)}))


if __name__ == "__main__":
    main()
