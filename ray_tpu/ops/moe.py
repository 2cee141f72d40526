"""Mixture-of-Experts ops: two routed feed-forwards.

``moe_ffn`` is the GShard/Switch formulation for TRAINING under expert
parallelism: dispatch and combine are einsums against one-hot capacity
tensors (static shapes, MXU-friendly, no gathers), and with the expert dim
of ``wi``/``wo`` sharded on the ``ep`` mesh axis XLA's SPMD partitioner
emits the token all-to-all. It is NOT exact: an expert takes at most
``capacity`` tokens and DROPS the overflow (the residual stream carries
those tokens unchanged), it renormalises the chosen gates, and its
``[T, E, C]`` tensors grow with the square of T.

``moe_ffn_dropless`` is EXACT, for serving and wherever a dropped token is
a wrong answer: float32 softmax over all experts, top-k, every chosen
expert applied to its token, nothing dropped, no ``[T, E, C]`` tensor. It
picks its formulation from the token count it is traced with, one number
(``DENSE_MAX_TOKENS``, ``expert_kernel_engages``): up to it every held
expert over every row, bound by one read of the experts' weights (a
decode step); past it the (token, choice) pairs sorted by held expert and
each expert over its own rows, one grouped matmul kernel
(``ops/grouped_expert_ffn.py``: the Pallas kernel where the program is
lowered for a TPU, a plain loop over the experts elsewhere), which reads
the weights of the experts that got a row once and computes the tiles
that hold one. It is also the expert layer of ONE CHIP under
expert parallelism: told which experts it holds (expert stacks narrower
than the router, and the first held expert's index), it routes over all
of them and computes the held experts' part of the result, without the
exchange that would add the other chips' parts. Its experts are of the
form it is told (``EXPERT_FORMS``): a SwiGLU of three matrices, the same
three with a ReLU gate (a ReGLU), or two matrices with a squared ReLU
between them and no gate. Its two halves stand alone too: ``moe_route``
scores rows and chooses, ``moe_experts`` applies a choice, so that a block
whose router reads the layer's INPUT makes the choice before its attention
and uses it after (``moe_ffn_dropless`` is the two in one call, the router
scoring ``x``). (Reference has NO MoE implementation — SURVEY.md §2c row EP.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.ops.grouped_expert_ffn import (grouped_expert_ffn_kernel,
                                            grouped_expert_ffn_reference,
                                            hidden_activation)


def router_topk(
    logits,             # [T, E] fp32
    *,
    top_k: int,
    capacity: int,
):
    """Top-k gating with per-expert capacity (GShard algorithm).

    Returns (dispatch [T, E, C] bool-ish float, combine [T, E, C] float,
    aux_loss scalar). Tokens over capacity are dropped (their combine weight
    is 0 — the residual stream carries them unchanged).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # top-k experts per token
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)     # [T, K]
    # renormalize the chosen gates
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # position of each token within its expert's queue, per choice slot
    dispatch = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    combine = jnp.zeros((t, e, capacity), dtype=jnp.float32)
    # running per-expert counts; iterate over the k slots (k is tiny/static)
    counts = jnp.zeros((e,), dtype=jnp.int32)
    for slot in range(top_k):
        idx = gate_idx[:, slot]                            # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)   # [T, E]
        # position within expert queue = tokens for same expert before me
        pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # [T, E]
        pos = jnp.sum(pos_in_expert * onehot, axis=1) + counts[idx]  # [T]
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T, C]
        contrib = (
            onehot.astype(jnp.float32)[:, :, None]
            * pos_oh[:, None, :]
            * keep.astype(jnp.float32)[:, None, None]
        )
        dispatch = dispatch + contrib
        combine = combine + contrib * gate_vals[:, slot][:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)

    # Load-balancing auxiliary loss (GShard/Mixtral): E * sum(f_i * p_i)
    # where f_i counts ALL top-k assignments, not just slot 0 — an expert
    # that is systematically every token's second choice must still feel
    # gradient pressure.
    me = jnp.mean(probs, axis=0)                            # mean router prob
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=1),
        axis=0,
    ) / top_k                                               # fraction routed
    aux_loss = e * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def moe_ffn(
    x,                  # [T, D] tokens (flattened batch*seq)
    router_w,           # [D, E]
    wi_gate,            # [E, D, F]
    wi_up,              # [E, D, F]
    wo,                 # [E, F, D]
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
):
    """SwiGLU expert FFN with top-k routing. Returns (out [T, D], aux_loss).

    All expert compute is einsum over the expert dim; shard wi/wo on
    ``ep`` to get expert parallelism (all-to-all inserted by XLA).
    """
    t, d = x.shape
    e = router_w.shape[1]
    capacity = max(1, int(capacity_factor * t * top_k / e))

    with jax.named_scope(scopes.MOE_ROUTER):
        logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
        dispatch, combine, aux = router_topk(
            logits, top_k=top_k, capacity=capacity
        )

    dtype = x.dtype
    with jax.named_scope(scopes.MOE_DISPATCH):
        expert_in = jnp.einsum(
            "td,tec->ecd", x, dispatch.astype(dtype),
            preferred_element_type=jnp.float32).astype(dtype)
    with jax.named_scope(scopes.MOE_EXPERTS):
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, wi_gate,
                       preferred_element_type=jnp.float32)
        ) * jnp.einsum("ecd,edf->ecf", expert_in, wi_up,
                       preferred_element_type=jnp.float32)
        h = h.astype(dtype)
        expert_out = jnp.einsum(
            "ecf,efd->ecd", h, wo,
            preferred_element_type=jnp.float32).astype(dtype)
    with jax.named_scope(scopes.MOE_COMBINE):
        out = jnp.einsum("ecd,tec->td", expert_out, combine.astype(dtype),
                         preferred_element_type=jnp.float32)
        return out.astype(dtype), aux


# Up to this many tokens ``moe_ffn_dropless`` runs every held expert over
# every token and weights the result (zero for an expert not chosen); past
# it, it sorts the (token, choice) pairs by held expert and runs the grouped
# kernel over the rows each expert got (``ops/grouped_expert_ffn.py``). One
# layer's call on a v5e, ms, at the four expert widths the benchmark runs
# with about each cell's share of choices on a held expert (PERF.md, PR 44;
# ``scripts/sweep_expert_formulations.py``; sorted from 1,024 rows as PR
# 63 left it, the rows coming back with the choices on the major axis),
# every-expert / sorted:
#
#   rows  64x2688x1856     64x3072x1024     32x5120x1536     64x2048x1024
#         relu2, 6/128     swiglu, 10/256   swiglu, 8/256    swiglu, 8/64
#    128   1.77 /  1.89     1.76 /  1.81     2.11 /  2.21     1.16 /  1.28
#    256   2.01 /  1.95     1.87 /  1.92     2.29 /  2.42     1.48 /  1.38
#    512   3.62 /  2.22     3.59 /  2.49     4.28 /  3.08     2.26 /  1.61
#   1024   7.24 /  2.76     6.88 /  3.10     8.42 /  3.88     4.46 /  2.26
#   2048  14.55 /  3.63    13.60 /  4.31    16.87 /  5.50     8.87 /  3.45
#   4096  28.96 /  5.39    27.09 /  6.92    33.82 /  8.94    17.60 /  5.56
#
# Every-expert reads the held experts' weights once (0.8-1.5 GB: 1.0-1.9 ms
# at the chip's bandwidth) and is bound by that read up to 256 rows, by its
# operations (held experts / chosen experts times the needed ones, at the
# chip's peak) from 512. The kernel reads the touched experts' weights once
# too (its two calls alone: 1.8 / 1.7 / 2.1 / 1.2 ms up to 512 rows, 85% of
# the bandwidth) and what grows with the rows is the sort, the gather of
# the pairs' rows and their weighted sum back (0.3-0.8 ms at 512 rows,
# 1.5-4.2 at 4,096). The two cross between 256 and 512 rows at every width.
# The line stays above 128, the most slots a cell decodes with: every decode
# program is every-expert's. (XLA's own grouped matmul over ragged groups,
# which the op ran past 1,024 rows until PR 44, pads every group to its
# tile: 2.6-4.8 ms at 128 rows and 8.4-12.6 at 4,096 at the three widths of
# whole lanes, 14-28 ms at the first, whose up stack it copies.)
DENSE_MAX_TOKENS = 256

# What one expert computes of a token ``x``, by the matrices it has:
# ``swiglu``: ``(silu(x @ gate) * (x @ up)) @ down``; ``reglu``: ``(relu(x
# @ gate) * (x @ up)) @ down``; ``relu2``: ``relu(x @ up) ** 2 @ down``,
# which has no gate (``wi_gate`` is None). A gated form's gate activation:
EXPERT_FORMS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu, "relu2": None}


def moe_route(
    rows,               # [T, D]: what the router scores
    router_w,           # [D, E]
    *,
    top_k: int,
    norm_topk_prob: bool = False,
    routed_scale: float = 1.0,
    scoring: str = "softmax",
    choice_bias=None,   # [E] float32, added to the scores for the choice
    norm_eps: float | None = None,  # added to the chosen scores' sum
):
    """The router's choice for each of ``rows``: (weights [T, K] float32,
    experts [T, K] int32 among the router's E), as ``moe_ffn_dropless``
    says. What ``moe_experts`` takes, here or further down the block."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', "
                         f"got {scoring!r}")
    with jax.named_scope(scopes.MOE_ROUTER):
        # true float32: the chip's default would round the products to
        # bf16 and now and then pick another k-th expert than float32 does
        logits = jnp.dot(rows.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = (jax.nn.softmax(logits, axis=-1) if scoring == "softmax"
                 else jax.nn.sigmoid(logits))
        if choice_bias is None:
            gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [T, K]
        else:
            _, gate_idx = jax.lax.top_k(
                probs + choice_bias.astype(jnp.float32), top_k)
            gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
        if norm_topk_prob:
            total = jnp.sum(gate_vals, axis=-1, keepdims=True)
            if norm_eps is not None:
                total = total + norm_eps    # the block's own, as published
            elif scoring == "sigmoid":
                total = total + 1e-20    # sigmoids can all be 0; a
            gate_vals = gate_vals / total   # softmax's top-k cannot
        if routed_scale != 1.0:
            gate_vals = gate_vals * routed_scale
    return gate_vals, gate_idx


def moe_experts(
    x,                  # [T, D] tokens (flattened batch*seq)
    choice,             # ``moe_route``'s (weights, experts) of the T rows
    wi_gate,            # [H, D, F]: the H <= E experts held here (None
    wi_up,              # [H, D, F]   where the experts' form has no gate)
    wo,                 # [H, F, D]
    *,
    n_experts: int,     # E, the router's width
    first_expert: int = 0,
    valid=None,         # [T] bool: rows that are tokens (None: all)
    form: str = "swiglu",   # one expert's form: ``EXPERT_FORMS``
    layer=None,         # the stacks are [L, H, ..]: this call is layer's
):
    """The held experts' weighted sum for a choice made of the same T
    rows (of ``x`` itself, or of other rows of theirs: the layer's input
    where the router reads that). Returns (out [T, D], load [H]); every
    argument as ``moe_ffn_dropless`` says."""
    if form not in EXPERT_FORMS or (wi_gate is None) != (form == "relu2"):
        raise ValueError(f"form must be one of {tuple(EXPERT_FORMS)}, with "
                         f"no gate for 'relu2' alone; got {form!r} and "
                         f"wi_gate {'None' if wi_gate is None else 'given'}")
    gate_vals, gate_idx = choice
    t = x.shape[0]
    e = wi_up.shape[-3]
    dtype = x.dtype
    gate_act = EXPERT_FORMS[form]       # None: the form has no gate
    with jax.named_scope(scopes.MOE_ROUTER):
        if e < n_experts:
            # a share: experts by their place in the stacks held here, and
            # every absent one as ``e``, which is no expert's place
            gate_idx = gate_idx - first_expert
            gate_idx = jnp.where((gate_idx >= 0) & (gate_idx < e), gate_idx, e)
        chosen = gate_idx[:, :, None] == jnp.arange(e)        # [T, K, H]
        if valid is not None:
            chosen = chosen & valid[:, None, None]
        load = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)  # [E]
    with jax.named_scope(scopes.MOE_EXPERTS):
        if not expert_kernel_engages(t):
            if layer is not None:
                wi_gate, wi_up, wo = (w if w is None else w[layer]
                                      for w in (wi_gate, wi_up, wo))
            weights = jnp.sum(jnp.where(chosen, gate_vals[:, :, None], 0.0),
                              axis=1)                          # [T, E]
            out = _experts_all(x, weights, wi_gate, wi_up, wo, gate_act)
        else:
            if layer is None:       # one layer's stacks: a run of one
                layer = 0
                wi_gate, wi_up, wo = (w if w is None else w[None]
                                      for w in (wi_gate, wi_up, wo))
            out = _experts_grouped(x, gate_vals, gate_idx, valid, load,
                                   wi_gate, wi_up, wo, layer, gate_act)
    return out.astype(dtype), load


def moe_ffn_dropless(
    x,                  # [T, D] tokens (flattened batch*seq)
    router_w,           # [D, E]
    wi_gate,            # [H, D, F]: the H <= E experts held here (None
    wi_up,              # [H, D, F]   where the experts' form has no gate)
    wo,                 # [H, F, D]
    *,
    top_k: int,
    norm_topk_prob: bool = False,
    routed_scale: float = 1.0,
    first_expert: int = 0,
    valid=None,         # [T] bool: rows that are tokens (None: all)
    scoring: str = "softmax",
    choice_bias=None,   # [E] float32, added to the scores for the choice
    form: str = "swiglu",   # one expert's form: ``EXPERT_FORMS``
    layer=None,         # the stacks are [L, H, ..]: this call is layer's
):
    """Exact routed feed-forward. Returns (out [T, D], load [H]).

    ``p = softmax(float32(x) @ router_w)`` over all E experts (with
    ``scoring="sigmoid"`` each expert's sigmoid of its own logit); the
    ``top_k`` largest and their experts (with ``choice_bias`` the
    ``top_k`` of largest ``p + choice_bias``, weighed by ``p`` alone: a
    correction that balances the load moves the choice and no weight);
    the weights are those
    probabilities as they are, or divided by their sum with
    ``norm_topk_prob``, times ``routed_scale``;
    ``out = sum_k p_k * (silu(x @ gate_k) * (x @ up_k)) @ down_k`` (with
    ``form="reglu"`` the gate's activation is a ReLU; with
    ``form="relu2"``: ``sum_k p_k * relu(x @ up_k) ** 2 @ down_k``) over
    those of a token's chosen experts that are HELD here: experts
    ``first_expert`` to ``first_expert + H`` of the router's E, H the
    length of the expert stacks (all of them where H = E). A choice that
    falls on an absent expert adds nothing: its part is another chip's.
    Rows that ``valid`` marks as padding go to no expert and come out
    zero. ``load`` counts the (token, choice) pairs each held expert got
    (int32). With ``layer`` (a traced scalar) the expert stacks are those
    of a run of layers, [L, H, ..], and the call is that layer's: what a
    program that scans the run hands over, so that the grouped kernel
    reads the layer's weights where they lie (a layer sliced out of the
    scan's stacks to feed a kernel is a copy of it). A block whose router
    reads other rows than its experts (the layer's input, with the
    experts behind the attention) calls ``moe_route`` there and
    ``moe_experts`` here.
    """
    choice = moe_route(
        x, router_w, top_k=top_k, norm_topk_prob=norm_topk_prob,
        routed_scale=routed_scale, scoring=scoring, choice_bias=choice_bias)
    return moe_experts(x, choice, wi_gate, wi_up, wo,
                       n_experts=router_w.shape[1],
                       first_expert=first_expert, valid=valid, form=form,
                       layer=layer)


def expert_kernel_engages(rows: int) -> bool:
    """Whether a call traced with ``rows`` tokens computes the held
    experts over the rows routed to them (``_experts_grouped``) and not
    every held expert over every row: the traced token count alone."""
    return rows > DENSE_MAX_TOKENS


def share_statistics(load, valid, rows: int, top_k: int) -> dict:
    """What a feed-forward that holds a SHARE of its router's experts
    reports of one call, scalars over the held experts, from their
    ``load`` [H] (``moe_ffn_dropless``): how many got a token, the
    busiest one's load over the mean load, and the share of the tokens'
    ``top_k`` choices that fell on a held expert (``valid``: the rows
    that are tokens, of ``rows``; None: all)."""
    with jax.named_scope(scopes.MOE_ROUTER):
        load = load.astype(jnp.float32)
        tokens = (jnp.float32(rows) if valid is None
                  else jnp.sum(valid, dtype=jnp.float32))
        return {
            "experts_touched": jnp.sum(load > 0, dtype=jnp.float32),
            "expert_load_max_over_mean":
                jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
            "routed_here_share":
                jnp.sum(load) / jnp.maximum(tokens * top_k, 1.0),
        }


def _experts_all(x, weights, wi_gate, wi_up, wo, gate_act):
    """Every expert over every token; ``weights`` [T, E] is zero where an
    expert was not chosen. The tokens are broadcast along the expert axis
    so that the up projections are plain batched matmuls, and the down
    projection contracts expert and width together, so nothing of shape
    [E, T, D] comes out of it."""
    xe = jnp.broadcast_to(x, (wi_up.shape[0],) + x.shape)       # [E, T, D]
    h = hidden_activation(
        lambda w: jnp.einsum("etd,edf->etf", xe, w,
                             preferred_element_type=jnp.float32),
        wi_gate, wi_up, gate_act)
    h = (h * weights.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("etf,efd->td", h, wo,
                      preferred_element_type=jnp.float32)


def _experts_grouped(x, gate_vals, gate_idx, valid, load, wi_gate, wi_up,
                     wo, layer, gate_act):
    """The chosen experts alone: the T x K (token, choice) pairs sorted by
    held expert (a choice on an absent expert and a padding row last, in
    no group), the experts over their groups (``ops.grouped_expert_ffn``:
    the kernel in a program lowered for a TPU, the plain loop elsewhere),
    and each token's K weighted results summed where they came from."""
    t, k = gate_idx.shape
    e = wi_up.shape[1]
    with jax.named_scope(scopes.MOE_DISPATCH):
        expert = gate_idx.reshape(t * k)
        if valid is not None:
            expert = jnp.where(jnp.repeat(valid, k), expert, e)
        order = jnp.argsort(expert)                # stable: pair -> row
        xs = x[order // k]                         # [T*K, D]
    ys = jax.lax.platform_dependent(
        xs, load, wi_gate, wi_up, wo, layer,
        tpu=functools.partial(grouped_expert_ffn_kernel, gate_act=gate_act),
        default=functools.partial(grouped_expert_ffn_reference,
                                  gate_act=gate_act))
    # each pair's row back to its token, the choices on the MAJOR axis: K
    # slabs of [T, D], so that splitting the gathered [K*T, D] rows moves
    # nothing (K beside D would be a tiled axis: a copy of every float32
    # row into tiles of 8, PERF.md PR 63). A pair in no group (its row
    # holds anything) adds nothing. Weighing and masking after the gather
    # fuse into the sum: no pass of their own over the rows
    with jax.named_scope(scopes.MOE_COMBINE):
        back = jnp.argsort(order).reshape(t, k).T  # [K, T]: pair -> row
        held = (expert < e).reshape(t, k).T[:, :, None]
        ys = ys[back.reshape(k * t)].reshape(k, t, -1)
        ys = ys * gate_vals.T[:, :, None]
        return jnp.sum(jnp.where(held, ys, 0.0), axis=0)
