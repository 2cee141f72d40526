"""Time one sliding layer's prefill attention at the serving cells' shapes.

A cold document of ``serve-brief-gen`` (one row of 8,192 query rows, 28
heads on 4 KV heads, a window of 4,096 keys over a 64-page table) and two
cold files of ``serve-code-gen`` (2,048 rows each, 72 heads on 8, a window
of 512 over 16 pages): the plain formulation (blocks of queries over the
gathered pages of their windows) against the prefill kernel under the same
window, with how far the two disagree on the chip, and the kernel without
a window (a full layer's walk over the same pages) beside them. PERF.md
section 5's table of PR 56 is this script's output. Run on the chip:

    python scripts/sweep_prefill_window.py [--toy]
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

from ray_tpu.ops import paged_prefill_attention as ppa  # noqa: E402

# rows, query rows, valid of them, heads, KV heads, head size, page, table
# pages, pool pages, window
CELLS = {
    "brief-cold-1x8192": (1, 8192, 6000, 28, 4, 128, 128, 64, 2304, 4096),
    "code-cold-2x2048": (2, 2048, 1800, 72, 8, 128, 128, 16, 2304, 512),
}
TOY = {"toy": (2, 64, 50, 14, 2, 128, 16, 8, 40, 24)}


def _time(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def sweep(name, shape, iters, interpret):
    n, t, valid, heads, nkv, hd, page, wp, pages, window = shape
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 3)
    k, v = (jax.random.normal(key, (2, pages, page, nkv, hd), jnp.bfloat16)
            for key in keys[:2])
    q = jax.random.normal(keys[2], (n, t, heads, hd), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pages)[:n * wp].reshape(n, wp),
                        jnp.int32)
    one = jnp.ones((2, 1, 1, 1), jnp.float32)
    args = (q, k, v, one, one, jnp.int32(1), table,
            jnp.zeros((n,), jnp.int32), jnp.full((n,), valid, jnp.int32))
    kernel = functools.partial(ppa.paged_prefill_attention_kernel,
                               interpret=interpret)
    calls = {
        "plain_ms": jax.jit(functools.partial(
            ppa.paged_prefill_attention_reference, window=window)),
        "kernel_ms": jax.jit(functools.partial(kernel, window=window)),
        "kernel_full_ms": jax.jit(kernel)}
    out = {"cell": name, "window": window,
           "engages": ppa.kernel_engages(q.shape, k, wp, window),
           "plain_block": ppa.query_block(n, t, heads, wp * page, window)}
    out.update({what: _time(fn, args, iters) for what, fn in calls.items()})
    got, want = (np.asarray(calls[c](*args), np.float32)[:, :valid]
                 for c in ("kernel_ms", "plain_ms"))
    out["max_abs_diff"] = float(np.abs(got - want).max())
    # query-key pairs a head under the window, and the matmuls' share of
    # the chip's 197 TFLOP/s that the kernel's time is
    pairs = sum(min(i + 1, window) for i in range(valid)) * n
    out["roofline_pct"] = (4.0 * pairs * heads * hd / 197e12
                           / (out["kernel_ms"] * 1e-3) * 100)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true",
                    help="tiny shapes in interpret mode (off the chip)")
    ap.add_argument("--iters", type=int, default=10)
    a = ap.parse_args()
    for name, shape in (TOY if a.toy else CELLS).items():
        print(json.dumps(sweep(name, shape, 1 if a.toy else a.iters, a.toy)),
              flush=True)


if __name__ == "__main__":
    main()
