"""Time the state kernel alone at the three published head shapes.

A layer's pass over every slot's recurrent state where it lies in the
stack (``ops/ssm.py:ssm_state_step_kernel``), at ``serve-assist-gen``'s,
``serve-reason-gen``'s and ``serve-instruct-gen``'s stacks: milliseconds
a layer-step and GB/s (the state read once and written once), beside the
kernel's launch around other bodies: the FLOOR (a block copied through,
``y`` zero: the grid's copies alone) and the body in PARTS (the update
without ``y``; ``y`` by a reduction over lanes a head and a column
store, which was the body until PR 66; the same with ``dtx`` from a row,
so without its lane broadcasts). A body is on the floor when its
cross-lane work hides under the block's copies: the lane broadcasts of
``dtx`` alone do, the lane reductions alone do, the two together did not
at 8-tile heads (PERF.md, Findings, PR 66). Run on the chip, for the
next head shape:

    PYTHONPATH=. python scripts/sweep_state_kernel.py [--toy]
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import ssm

# layers, slots, heads, head width, state size, groups
SHAPES = {
    "granite-4.0-h-small": (2, 64, 128, 64, 128, 1),
    "nemotron-3-nano": (2, 128, 64, 64, 128, 8),
    "falcon-h1-34b": (2, 128, 32, 128, 256, 2),
}
TOY = {"toy-narrow": (2, 2, 32, 64, 128, 4),
       "toy-wide": (2, 2, 8, 128, 256, 2)}


def floor(layer_ref, active_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
          y_ref, o_ref, *, heads, per_group):
    o_ref[...] = s_ref[...]
    y_ref[...] = jnp.zeros_like(y_ref)


def _heads(s_ref, decay_ref, b_ref, c_ref, heads, per_group):
    """(j, the head's decay, its group's b and c rows) of a block."""
    hb = s_ref.shape[0]
    slot = pl.program_id(0)
    first = pl.program_id(1) * hb
    for j in range(hb):
        group = (first + j) // per_group
        yield (j, decay_ref[slot * heads + first + j], b_ref[group],
               c_ref[group])


def update_only(layer_ref, active_ref, decay_ref, dtx_ref, b_ref, c_ref,
                s_ref, y_ref, o_ref, *, heads, per_group):
    dtx = dtx_ref[...].T
    for j, decay, b, _ in _heads(s_ref, decay_ref, b_ref, c_ref, heads,
                                 per_group):
        o_ref[j] = decay * s_ref[j] + dtx[:, j:j + 1] * b
    y_ref[...] = jnp.zeros_like(y_ref)


def _y_by_lanes(new_of):
    """The body until PR 66 around a head's update ``new_of``: ``y`` a
    head by a reduction over lanes, stored as a column, the columns
    transposed at the block's end."""
    def body(layer_ref, active_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
             y_ref, o_ref, *, heads, per_group):
        # (read outside the scope: the grid's indices are not seen in it)
        block_heads = list(_heads(s_ref, decay_ref, b_ref, c_ref, heads,
                                  per_group))

        def block(y_cols):
            dtx = dtx_ref[...].T
            for j, decay, b, c in block_heads:
                new = new_of(decay, s_ref[j], dtx[:, j:j + 1], b, c)
                o_ref[j] = new
                y_cols[:, j:j + 1] = jnp.sum(new * c, axis=-1,
                                             keepdims=True)
            y_ref[...] = y_cols[...].T
        pl.run_scoped(block, pltpu.VMEM(y_ref.shape[::-1], jnp.float32))
    return body


BODIES = {
    "kernel": ssm._state_kernel,
    "floor": floor,
    "update_only": update_only,
    "y_by_lanes": _y_by_lanes(
        lambda decay, s, dtx, b, c: decay * s + dtx * b),
    # (c's row stands for dtx: a broadcast over sublanes, none over lanes)
    "y_by_lanes_dtx_from_a_row": _y_by_lanes(
        lambda decay, s, dtx, b, c: decay * s + c * b),
}


def draw(dims):
    layers, slots, heads, width, size, groups = dims
    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (slots, heads, width)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (slots, groups, size)).astype(jnp.bfloat16)
    c = jax.random.normal(ks[4], (slots, groups, size)).astype(jnp.bfloat16)
    states = jax.random.normal(ks[5], (layers, slots, heads, width, size))
    return (x, dt, a, b, c), states


def time_body(body, dims, iters, interpret, reps=3):
    """The least of ``reps`` timings of ``iters`` layer-steps chained
    through the stack (donated, so each is in place) inside one program;
    for the kernel itself, how far it lies from the plain formulation."""
    held, states = draw(dims)
    active, layer = jnp.ones((dims[1],), bool), jnp.int32(1)
    out = {}
    if body is ssm._state_kernel:
        want_y, want = jax.jit(ssm.ssm_state_step_reference)(
            *held, states, layer, active)
        got_y, got = jax.jit(functools.partial(
            ssm.ssm_state_step_kernel, interpret=interpret))(
            *held, states, layer, active)
        out["y_gap"] = float(jnp.abs(got_y - want_y).max())
        out["state_gap"] = float(jnp.abs(got - want).max())
        del want, got

    def run(states, held):
        def step(carry, _):
            states, seen = carry
            y, states = ssm._state_call(body, *held, states, layer, active,
                                        interpret=interpret)
            return (states, seen + y[0, 0, 0]), None
        return lax.scan(step, (states, jnp.float32(0)), None,
                        length=iters)[0]

    run = jax.jit(run, donate_argnums=(0,))
    states, _ = jax.block_until_ready(run(states, held))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        states, _ = jax.block_until_ready(run(states, held))
        times.append((time.perf_counter() - t0) / iters)
    ms = min(times) * 1e3
    moved = 2 * 4 * np.prod(dims[1:5])
    return {**out, "ms": round(ms, 4), "gb_s": round(moved / ms / 1e6, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--toy", action="store_true",
                    help="tiny stacks in interpret mode, off the chip")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.toy):
        raise SystemExit("a time comes from the chip: --toy rehearses")
    out = {"device": jax.devices()[0].device_kind}
    for shape, dims in (TOY if args.toy else SHAPES).items():
        out[shape] = {
            "block_heads": ssm._block_heads(dims[2], 4 * dims[3] * dims[4]),
            **{name: time_body(body, dims, 2 if args.toy else args.iters,
                               not on_tpu)
               for name, body in BODIES.items()}}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
