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
picks its formulation from the token count it is traced with
(``DENSE_MAX_TOKENS``). It is also the expert layer of ONE CHIP under
expert parallelism: told which experts it holds (expert stacks narrower
than the router, and the first held expert's index), it routes over all
of them and computes the held experts' part of the result, without the
exchange that would add the other chips' parts. Its experts are of the
form it is told (``EXPERT_FORMS``): a SwiGLU of three matrices, or two
matrices with a squared ReLU between them and no gate. (Reference has NO
MoE implementation — SURVEY.md §2c row EP.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    dispatch, combine, aux = router_topk(
        logits, top_k=top_k, capacity=capacity
    )

    dtype = x.dtype
    expert_in = jnp.einsum("td,tec->ecd", x, dispatch.astype(dtype),
                           preferred_element_type=jnp.float32).astype(dtype)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, wi_gate,
                   preferred_element_type=jnp.float32)
    ) * jnp.einsum("ecd,edf->ecf", expert_in, wi_up,
                   preferred_element_type=jnp.float32)
    h = h.astype(dtype)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo,
                            preferred_element_type=jnp.float32).astype(dtype)
    out = jnp.einsum("ecd,tec->td", expert_out, combine.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(dtype), aux


# Up to this many tokens ``moe_ffn_dropless`` runs every expert over every
# token and weights the result (zero for an expert not chosen); past it, it
# sorts the (token, choice) pairs by expert and runs grouped matmuls over
# the chosen experts alone. On a v5e at OLMoE's widths (64 experts of 2048 x
# 1024, 8 a token; PERF.md, PR 28) the first reads the experts' weights at
# nine tenths of the chip's bandwidth up to 128 tokens (1.1 ms a layer) and
# is bound by its 64 / 8 times the operations from about 256 (4.4 ms at
# 1024, at the chip's peak); the second, whose grouped matmul XLA pads to
# its tile group by group, takes 4-6 ms up to 1024 tokens and wins from
# about 2048 (8.4 ms), and its temporaries do not grow with E x F a token.
DENSE_MAX_TOKENS = 1024

# What one expert computes of a token ``x``, by the matrices it has:
# ``swiglu``: ``(silu(x @ gate) * (x @ up)) @ down``; ``relu2``:
# ``relu(x @ up) ** 2 @ down``, which has no gate (``wi_gate`` is None).
EXPERT_FORMS = ("swiglu", "relu2")


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
    ``form="relu2"``: ``sum_k p_k * relu(x @ up_k) ** 2 @ down_k``) over
    those of a token's chosen experts that are HELD here: experts
    ``first_expert`` to ``first_expert + H`` of the router's E, H the
    length of the expert stacks (all of them where H = E). A choice that
    falls on an absent expert adds nothing: its part is another chip's.
    Rows that ``valid`` marks as padding go to no expert and come out
    zero. ``load`` counts the (token, choice) pairs each held expert got
    (int32).
    """
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', "
                         f"got {scoring!r}")
    if form not in EXPERT_FORMS or (wi_gate is None) != (form == "relu2"):
        raise ValueError(f"form must be one of {EXPERT_FORMS}, with a gate "
                         f"for 'swiglu' alone; got {form!r} and wi_gate "
                         f"{'None' if wi_gate is None else 'given'}")
    t, d = x.shape
    e = wi_up.shape[0]
    dtype = x.dtype
    with jax.named_scope("moe_router"):
        # true float32: the chip's default would round the products to
        # bf16 and now and then pick another k-th expert than float32 does
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
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
            if scoring == "sigmoid":
                total = total + 1e-20    # sigmoids can all be 0; a
            gate_vals = gate_vals / total   # softmax's top-k cannot
        if routed_scale != 1.0:
            gate_vals = gate_vals * routed_scale
        if e < router_w.shape[1]:
            # a share: experts by their place in the stacks held here, and
            # every absent one as ``e``, which is no expert's place
            gate_idx = gate_idx - first_expert
            gate_idx = jnp.where((gate_idx >= 0) & (gate_idx < e), gate_idx, e)
        chosen = gate_idx[:, :, None] == jnp.arange(e)        # [T, K, H]
        if valid is not None:
            chosen = chosen & valid[:, None, None]
        load = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)  # [E]
    with jax.named_scope("moe_experts"):
        if t <= DENSE_MAX_TOKENS:
            weights = jnp.sum(jnp.where(chosen, gate_vals[:, :, None], 0.0),
                              axis=1)                          # [T, E]
            out = _experts_all(x, weights, wi_gate, wi_up, wo)
        else:
            out = _experts_grouped(x, gate_vals, gate_idx, valid, load,
                                   wi_gate, wi_up, wo)
    return out.astype(dtype), load


def share_statistics(load, valid, rows: int, top_k: int) -> dict:
    """What a feed-forward that holds a SHARE of its router's experts
    reports of one call, scalars over the held experts, from their
    ``load`` [H] (``moe_ffn_dropless``): how many got a token, the
    busiest one's load over the mean load, and the share of the tokens'
    ``top_k`` choices that fell on a held expert (``valid``: the rows
    that are tokens, of ``rows``; None: all)."""
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


def _hidden(into, wi_gate, wi_up):
    """An expert's hidden activations in float32, by its form: ``into(w)``
    is the tokens' product with the stack ``w``. With a gate ``silu(x @
    gate) * (x @ up)``; without one ``relu(x @ up) ** 2``."""
    if wi_gate is None:
        return jnp.square(jax.nn.relu(into(wi_up)))
    return jax.nn.silu(into(wi_gate)) * into(wi_up)


def _experts_all(x, weights, wi_gate, wi_up, wo):
    """Every expert over every token; ``weights`` [T, E] is zero where an
    expert was not chosen. The tokens are broadcast along the expert axis
    so that the up projections are plain batched matmuls, and the down
    projection contracts expert and width together, so nothing of shape
    [E, T, D] comes out of it."""
    xe = jnp.broadcast_to(x, (wi_up.shape[0],) + x.shape)       # [E, T, D]
    h = _hidden(lambda w: jnp.einsum("etd,edf->etf", xe, w,
                                     preferred_element_type=jnp.float32),
                wi_gate, wi_up)
    h = (h * weights.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("etf,efd->td", h, wo,
                      preferred_element_type=jnp.float32)


def _experts_grouped(x, gate_vals, gate_idx, valid, load, wi_gate, wi_up,
                     wo):
    """The chosen experts alone: the T x K (token, choice) pairs sorted by
    expert (padding rows last, in no group), three grouped matmuls
    (``jax.lax.ragged_dot``) over the sorted rows, and each token's K
    weighted results summed where they came from."""
    t, k = gate_idx.shape
    e = wi_up.shape[0]
    expert = gate_idx.reshape(t * k)
    if valid is not None:
        expert = jnp.where(jnp.repeat(valid, k), expert, e)
    order = jnp.argsort(expert)                    # stable: pair -> row
    xs = x[order // k]                             # [T*K, D]
    h = _hidden(lambda w: jax.lax.ragged_dot(
        xs, w, load, preferred_element_type=jnp.float32), wi_gate, wi_up)
    ys = jax.lax.ragged_dot(h.astype(x.dtype), wo, load,
                            preferred_element_type=jnp.float32)
    # rows past the last group belong to no expert: whatever they hold
    # must not reach a sum
    weight = gate_vals.reshape(t * k)[order]
    ys = jnp.where((expert[order] < e)[:, None], ys * weight[:, None], 0.0)
    back = jnp.argsort(order)                      # row -> pair
    return jnp.sum(ys[back].reshape(t, k, -1), axis=1)
