"""Time the formulations of the routed experts at the benchmark's widths.

One ``moe_ffn_dropless`` call a layer (router, experts, the sum over a
token's choices) at each of the seven expert widths ``BENCHMARK.json`` runs
and 128 to 8,192 rows, with random weights and so about the held share of
choices a cell has: every held expert over every row (``all``), the
grouped kernel over the rows sorted by expert (``grouped``; ``kernel`` is
its two Pallas calls alone, without sort, gather and sum; ``way`` is
``grouped`` less ``kernel``: the rows' way there and back, with the
router), and XLA's own ``jax.lax.ragged_dot`` over the same sorted rows
(``ragged``: what the op ran past 1,024 rows before PR 44, kept here as
the yardstick). The table in ``ops/moe.py`` over ``DENSE_MAX_TOKENS`` and
those of PERF.md section 5 are this script's output. Run on the chip:

    python scripts/sweep_expert_formulations.py [--widths nano,code,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_expert_ffn as gef
from ray_tpu.ops import moe

# name: experts the router scores, held here, model width, expert width,
# form, choices a token (the configurations under benchmark/configs)
WIDTHS = {
    "nano": (128, 64, 2688, 1856, "relu2", 6),
    "code": (256, 64, 3072, 1024, "swiglu", 10),
    "note": (256, 32, 5120, 1536, "swiglu", 8),
    "moe": (64, 64, 2048, 1024, "swiglu", 8),
    "brief": (64, 64, 2560, 768, "reglu", 6),
    "longqa": (128, 128, 2048, 768, "swiglu", 8),
    "assist": (72, 36, 4096, 768, "swiglu", 10),
    "toy": (8, 4, 128, 64, "swiglu", 2),      # to rehearse off the chip
}
ROWS = (128, 256, 512, 1024, 2048, 4096, 8192)


def _ragged(xs, load, wi_gate, wi_up, wo, layer, *, gate_act):
    h = gef.hidden_activation(lambda w: jax.lax.ragged_dot(
        xs, w[layer], load, preferred_element_type=jnp.float32),
        wi_gate, wi_up, gate_act)
    return jax.lax.ragged_dot(h.astype(xs.dtype), wo[layer], load,
                              preferred_element_type=jnp.float32)


def _time(step, x, weights, iters):
    """Seconds a call of ``step`` (x, weights -> array like x), ``iters``
    calls chained through their results inside one program."""
    run = jax.jit(lambda x, weights: jax.lax.scan(
        lambda c, _: (step(c, weights), None), x, None, length=iters)[0])
    jax.block_until_ready(run(x, weights))
    t0 = time.perf_counter()
    jax.block_until_ready(run(x, weights))
    return (time.perf_counter() - t0) / iters


def sweep(name, rows_list, iters, tiles, forms):
    total, held, d, f, form, top_k = WIDTHS[name]
    keys = jax.random.split(jax.random.key(0), 5)
    router = jax.random.normal(keys[0], (d, total), jnp.float32)
    scale = d ** -0.5

    def stack(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    gate = stack(keys[1], (held, d, f)) if form != "relu2" else None
    up = stack(keys[2], (held, d, f))
    down = stack(keys[3], (held, f, d))
    out = []
    for rows in rows_list:
        x = jax.random.normal(keys[4], (rows, d), jnp.bfloat16)

        def layer(x, weights):
            y, _ = moe.moe_ffn_dropless(
                x, router, *weights, top_k=top_k, form=form,
                norm_topk_prob=True)
            return (x + 1e-3 * y).astype(x.dtype)

        line = {"widths": name, "rows": rows}
        kernel = moe.grouped_expert_ffn_kernel
        for label, line_at, tpu in (
                [("all", rows, kernel)]
                + [(f"grouped_t{t}", 0,
                    lambda *a, t=t, **kw: kernel(*a, tile=t, **kw))
                   for t in tiles]
                + [("ragged", 0, _ragged)]):
            if label.split("_")[0] not in forms:
                continue
            moe.DENSE_MAX_TOKENS, moe.grouped_expert_ffn_kernel = line_at, tpu
            try:
                line[label + "_ms"] = round(1e3 * _time(
                    layer, x, (gate, up, down), iters), 4)
            except Exception as e:  # noqa: BLE001
                line[label + "_ms"] = f"{type(e).__name__}: {str(e)[:200]}"
        moe.grouped_expert_ffn_kernel = kernel
        # the two kernel calls alone, over as many rows a held expert as
        # the routing gives on average
        pairs = rows * top_k
        load = jnp.full((held,), pairs * held // total // held, jnp.int32)
        xs = jax.random.normal(keys[4], (pairs, d), jnp.bfloat16)
        interpret = jax.default_backend() != "tpu"
        for t in tiles:
            line[f"kernel_t{t}_ms"] = round(1e3 * _time(
                lambda xs, weights: (xs + 1e-3 * kernel(
                    xs, load, *(w if w is None else w[None]
                                for w in weights), 0, tile=t,
                    gate_act=moe.EXPERT_FORMS[form],
                    interpret=interpret)).astype(xs.dtype),
                xs, (gate, up, down), iters), 4)
            if isinstance(line.get(f"grouped_t{t}_ms"), float):
                line[f"way_t{t}_ms"] = round(
                    line[f"grouped_t{t}_ms"] - line[f"kernel_t{t}_ms"], 4)
        line["held_pairs"] = int(load.sum())
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths",
                    default="nano,code,note,moe,brief,longqa,assist")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--forms", default="all,grouped,ragged")
    ap.add_argument("--tiles", default=str(gef.ROW_TILE))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/expert_formulations.jsonl")
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "platform": jax.devices()[0].platform}), flush=True)
    lines = []
    for name in args.widths.split(","):
        lines += sweep(name, [int(r) for r in args.rows.split(",")],
                       args.iters, [int(t) for t in args.tiles.split(",")],
                       args.forms.split(","))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(line) + "\n" for line in lines)
