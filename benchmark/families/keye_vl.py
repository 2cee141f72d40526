"""Grouped-query sparse-expert decoders whose every layer picks the keys
it attends over, served by ``ray_tpu.models.keye_vl`` (Kwai-Keye
Keye-VL-2.0-30B-A3B, ``model_type`` ``KeyeVL2``, the text path of the
language model): the adapter from the published Hugging Face keys to the
program's config class, the plain reference of the block, and its
operation and byte counts (``benchmark/families/__init__.py`` says what a
family is).

The reference follows the published ``config.json``; every layer is the
same (``decoder_sparse_step`` 1, ``mlp_only_layers`` []). ``d`` =
``hidden_size``, RMSNorm in float32 at ``rms_norm_eps``. ``u = rms(x,
attn_norm)``; ``q = u Wq`` in ``num_attention_heads`` heads of
``head_dim``, ``k = u Wk`` and ``v = u Wv`` in ``num_key_value_heads``;
``q = rms(q, q_norm)`` and ``k = rms(k, k_norm)`` over each head's
numbers; rotary on both, rotate-half over the whole head at
``rope_theta``, the ``head_dim / 2`` frequency pairs taking their angle
from position axis 0, 1, 2 in sections of ``rope_scaling.mrope_section``
(for a text token the three are equal and this is plain rotary). The
indexer (``sa_config``): ``qI = u WIq`` in ``indexer_num_heads`` heads of
``indexer_head_dim``, ONE index key a token ``kI = layernorm(u WIk)`` for
all of them (``indexer_num_kv_heads`` 1), ``w = u WIw``, rotary over the
whole index head by axis 0; ``I(t, s) = sum_j w_j(t) relu(qI_j(t) .
kI(s))``; query ``t`` sees the ``topk`` keys of largest ``I`` among ``s <=
t`` (all of them while there are no more), ties to the lower position,
the same set for every head. Scores ``q . k / sqrt(head_dim)``, softmax
over the seen keys, ``heads / kv heads`` query heads a KV head; ``x +=
attn Wo``. Then ``g = rms(x, mlp_norm)``; router logits ``g Wr`` in
float32, softmax over all ``num_experts``, the ``num_experts_per_tok``
largest, divided by their sum (``norm_topk_prob``); ``x += sum_e p_e
(silu(g Wgate_e) * (g Wup_e)) Wdown_e``. Final RMSNorm; untied head.
Positive factors on ``I`` (DeepSeek-V3.2's ``1 / sqrt(dim)`` and ``1 /
sqrt(heads)``) change no ordering and are left out.

What ``config.json`` names and does not define is a NAMED DEPARTURE of
``logits``, with the reading taken as its default, so that the other
reading is one argument away (the configuration file lists each under
``assumed``):

- ``qk_norm="head"``: RMSNorm of q and k over each head, one weight
  vector of ``head_dim`` (the family's convention); ``"none"`` leaves
  both out;
- ``indexer="learned"``: the selection above; ``"none"`` lets a query
  see every key; ``topk=`` another count than ``sa_config.topk``;
- ``index_norm="layernorm"``: the index key's LayerNorm, weight and
  bias, eps 1e-6 (DeepSeek-V3.2's); ``"none"`` leaves it out;
- ``index_rope="whole"``: rotary over the index head's whole width at
  ``rope_theta`` by position axis 0; ``"none"`` leaves it out;
- ``chunks="tiling"``: ``q_chunk_size`` and ``kv_chunk_size`` are how the
  published code tiles the indexer's scores, and change no number: the
  reference ignores them (any other value is refused);
- ``mrope="sections"``: as above; ``"plain"`` takes every pair's angle
  from axis 0. ``axes`` [3, b, s] gives the tokens' three positions
  where they differ (an image's or a video's); None: text;
- ``norm_topk_prob=None``: the configuration's (true); ``False`` leaves
  the chosen probabilities as the softmax over all gave them.

The vision tower that the catalog's ``described_as`` mentions ("SigLIP-
class ViT 27L") has no key in ``config.json``'s language-model settings:
no width of it is known, and it is not run.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` holds the layers' weights stacked on a leading
axis; ``w_in`` holds the columns q | k | v | index queries | index key |
index weights), and imports nothing from the program. A layer at a time,
attention and the selection a block of queries at a time (a block's
scores, its index scores and its selection mask are all that exists at
once), the experts as a masked loop, the head a slice of the vocabulary
at a time, so that the check's 4,632 positions fit on the chip beside the
engine. On a TPU a float32 matrix multiplication runs in lower precision
unless told otherwise: ``logits`` runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256       # queries whose scores exist at once
INDEX_NORM_EPS = 1e-6   # the index key's LayerNorm (DeepSeek-V3.2's)


# -- the adapter: the one part that touches the program -----------------

def model_config(config: dict):
    from ray_tpu.models import keye_vl

    sparse = config["sa_config"]
    return keye_vl.KeyeVLConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        mrope_sections=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=sparse["indexer_num_heads"],
        index_dim=sparse["indexer_head_dim"], index_topk=sparse["topk"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import keye_vl

    return keye_vl.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, angles):
    """x [s, heads, r]: rotate pairs (i, i + r/2) of the last axis by
    ``angles`` [s, r/2] (rotate-half)."""
    r = x.shape[-1]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _angles(axes, width, theta, sections):
    """[s, width / 2]: each frequency pair's angle, from the position
    axis its section names (``axes`` [3, s]); ``sections`` None: from
    axis 0."""
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    if sections is None:
        return axes[0][:, None].astype(jnp.float32) * inv
    axis = jnp.concatenate([jnp.full((n,), i, jnp.int32)
                            for i, n in enumerate(sections)])
    return axes.astype(jnp.float32)[axis, :].T * inv


def _selected(scores, seen, topk):
    """[q, s] bool: for each query the ``topk`` seen keys of largest
    score, ties to the lower position; every seen key where there are no
    more than ``topk``."""
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return seen & (rank < topk)


def _attention(u, axes, p, *, heads, kv_heads, head_dim, eps, theta,
               sections, qk_norm, index, index_norm, index_rope):
    """One sequence's attention sublayer, u [s, d] the normed input and
    ``axes`` [3, s] its tokens' positions: float32 [s, heads x head_dim]
    before ``Wo``. ``index``: None, or (heads, width, topk) of the
    layer's indexer."""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    s = u.shape[0]
    qdim, kvdim = heads * head_dim, kv_heads * head_dim
    y = u @ f32("w_in")
    q = y[:, :qdim].reshape(s, heads, head_dim)
    k = y[:, qdim:qdim + kvdim].reshape(s, kv_heads, head_dim)
    v = y[:, qdim + kvdim:qdim + 2 * kvdim].reshape(s, kv_heads, head_dim)
    if qk_norm == "head":
        q = _rms_norm(q, f32("q_norm"), eps)
        k = _rms_norm(k, f32("k_norm"), eps)
    angles = _angles(axes, head_dim, theta, sections)
    q, k = _rope(q, angles), _rope(k, angles)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    if index is not None:
        hi, di, topk = index
        at = qdim + 2 * kvdim
        qi = y[:, at:at + hi * di].reshape(s, hi, di)
        ki = y[:, at + hi * di:at + hi * di + di]
        wi = y[:, at + hi * di + di:]
        if index_norm == "layernorm":
            mean = jnp.mean(ki, axis=-1, keepdims=True)
            ki = ((ki - mean) * jax.lax.rsqrt(
                jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
                + INDEX_NORM_EPS) * f32("index_norm")
                + f32("index_norm_bias"))
        if index_rope == "whole":
            turn = _angles(axes, di, theta, None)
            qi, ki = _rope(qi, turn), _rope(ki[:, None], turn)[:, 0]

    positions = jnp.arange(s)

    def block(first):
        rows = jnp.minimum(first + jnp.arange(QUERY_BLOCK), s - 1)
        seen = positions[None, :] <= positions[rows][:, None]
        if index is not None:
            dots = jnp.einsum("qhd,sd->qhs", qi[rows], ki)
            scores = jnp.sum(wi[rows][:, :, None] * jax.nn.relu(dots), 1)
            seen = _selected(scores, seen, topk)
        att = jnp.einsum("qhd,shd->hqs", q[rows], k) * head_dim ** -0.5
        att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", att, v)

    out = jax.lax.map(block, jnp.arange(0, s, QUERY_BLOCK))
    # (a tail block repeats the last row; the slice drops the repeats)
    return out.reshape(-1, qdim)[:s]


def _routed_ffn(g, p, *, top_k, norm_topk_prob):
    """g [s, d] float32 -> the routed experts' sum, float32: a softmax
    over all the router's logits, the ``top_k`` largest, divided by their
    sum where ``norm_topk_prob``; the experts as a loop over all of them,
    each applied to every token and kept where it is among the token's
    chosen."""
    probs = jax.nn.softmax(g @ p["router"].astype(jnp.float32), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k]
    chosen = probs >= kth[..., None]
    weight = jnp.where(chosen, probs, 0.0)                        # [s, E]
    if norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(y, expert):
        gate, up, down, w, on = expert
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        out = (jax.nn.silu(g @ gate) * (g @ up)) @ down
        return y + jnp.where(on[..., None], w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(g),
                        (p["wi_gate"], p["wi_up"], p["wo_e"],
                         jnp.moveaxis(weight, -1, 0),
                         jnp.moveaxis(chosen, -1, 0)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "sections", "qk_norm",
    "index", "index_norm", "index_rope", "top_k", "norm_topk_prob"))
def _layer(x, axes, p, *, eps, top_k, norm_topk_prob, **attention):
    """One decoder layer on x [b, s, d] float32, ``axes`` [3, b, s] its
    tokens' positions; p holds this layer's weights in their stored
    dtype."""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    u = _rms_norm(x, f32("attn_norm"), eps)
    attend = functools.partial(_attention, p=p, eps=eps, **attention)
    attn = jax.lax.map(lambda xs: attend(*xs),       # a sequence at a time
                       (u, jnp.moveaxis(axes, 1, 0)))
    x = x + attn @ f32("wo")
    g = _rms_norm(x, f32("mlp_norm"), eps)
    return x + jax.lax.map(functools.partial(
        _routed_ffn, p=p, top_k=top_k, norm_topk_prob=norm_topk_prob), g)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@functools.cache
def _head_slice():
    """``out`` [b, s, vocab] with the logits of ``width`` ids from
    ``first`` on written into their place. ``out`` is DONATED (off the CPU,
    which has no donation), so that the slices land in the one array: a
    loop inside one program is given the result twice, as its output and
    as its loop's value, 5.6 GB where the chip has 3."""
    def write(out, x, lm_head, first, *, width):
        w = jax.lax.dynamic_slice_in_dim(lm_head, first, width, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(jnp.float32), first, axis=2)

    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(write, static_argnames=("width",), donate_argnums=donate)


def _head(x, final_norm, lm_head, *, eps):
    """Float32 logits of x [b, s, d], a slice of the vocabulary at a time,
    each written into its place of the one result."""
    x = _final_norm(x, final_norm, eps=eps)
    vocab = lm_head.shape[1]
    slices = next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)
    out = jnp.zeros((*x.shape[:2], vocab), jnp.float32)
    for i in range(slices):
        out = _head_slice()(out, x, lm_head, i * (vocab // slices),
                            width=vocab // slices)
    return out


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def logits(config: dict, params: dict, tokens, *, axes=None, qk_norm="head",
           indexer="learned", topk=None, index_norm="layernorm",
           index_rope="whole", chunks="tiling", mrope="sections",
           norm_topk_prob=None) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    for name, value, known in (
            ("qk_norm", qk_norm, ("head", "none")),
            ("indexer", indexer, ("learned", "none")),
            ("index_norm", index_norm, ("layernorm", "none")),
            ("index_rope", index_rope, ("whole", "none")),
            ("chunks", chunks, ("tiling",)),
            ("mrope", mrope, ("sections", "plain"))):
        if value not in known:
            raise ValueError(f"{name} must be one of {known}, not {value!r}")
    sparse = config["sa_config"]
    index = None if indexer == "none" else (
        sparse["indexer_num_heads"], sparse["indexer_head_dim"],
        sparse["topk"] if topk is None else topk)
    kw = dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        sections=(tuple(config["rope_scaling"]["mrope_section"])
                  if mrope == "sections" else None),
        qk_norm=qk_norm, index=index, index_norm=index_norm,
        index_rope=index_rope, top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]
                            if norm_topk_prob is None else norm_topk_prob))
    tokens = jnp.asarray(tokens)
    if axes is None:
        axes = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), (3, *tokens.shape))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        blocks = params["blocks"]
        for i in range(config["num_hidden_layers"]):
            x = _layer(x, axes, jax.tree.map(lambda a, i=i: a[i], blocks),
                       **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def attention_params(m: dict) -> int:
    """A layer's attention weights: q, k, v and ``wo``."""
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def indexer_params(m: dict) -> int:
    """A layer's indexer: the index queries, the one index key and the
    head weights from the hidden state, and the key's LayerNorm."""
    hi, di = (m["sa_config"]["indexer_num_heads"],
              m["sa_config"]["indexer_head_dim"])
    return m["hidden_size"] * (hi * di + di + hi) + 2 * di


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["num_experts"]


def layer_params(m: dict) -> int:
    """Attention, the indexer, the router, every expert, the two norms of
    the stream and the two of q and k."""
    return (attention_params(m) + indexer_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m)
            + 2 * m["hidden_size"] + 2 * m["head_dim"])


def total_params(m: dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    head = 0 if m["tie_word_embeddings"] else d * v
    return m["num_hidden_layers"] * layer_params(m) + d * v + head + d


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def cache_bytes_per_token(m: dict) -> int:
    """What one token keeps over the layers, bf16: its K and V rows and
    its index key in each."""
    return m["num_hidden_layers"] * (
        kv_bytes_per_token_layer(m) + 2 * m["sa_config"]["indexer_head_dim"])


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def experts_touched(m: dict, counters: dict) -> float:
    """How many of a layer's experts one decode step's rows reach: the
    program's own count where the run's counters hold it
    (``experts_touched_mean``), else what the live slots' tokens reach
    when each picks its experts uniformly: E x (1 - (1 - k / E) ** n)."""
    counted = counters.get("experts_touched_mean")
    if counted is not None:
        return float(counted)
    k, e = m["num_experts_per_tok"], m["num_experts"]
    return e * (1.0 - (1.0 - k / e) ** _live_slots(counters))


def sparse_attention_bytes(m: dict, counters: dict) -> float:
    """Bytes one decode step's attention and selection must read,
    whatever implements them, bf16, over the layers: each live slot's
    ``min(length, topk)`` K and V rows and EVERY live index key. From the
    mean of the live contexts' sum (``live_kv_tokens_mean``) and the mean
    number of live slots: exact where every live context is on one side
    of ``topk``, else an upper bound no greater than either side's. For
    ``sparse_attn_roofline``; not ``attention_kv_bytes``, whose reader
    times one kernel."""
    live = counters.get("live_kv_tokens_mean", 0.0)
    selected = min(live, m["sa_config"]["topk"] * _live_slots(counters))
    return m["num_hidden_layers"] * (
        selected * kv_bytes_per_token_layer(m)
        + live * 2 * m["sa_config"]["indexer_head_dim"])


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the attention, indexer and
    head weights (bf16) and the routers (float32) once; the weights of
    the experts the step's rows reach (``experts_touched``); the rows of
    ``sparse_attention_bytes`` once. The engine reads every expert
    whatever the routing and every K/V page whatever the selection, so
    against this count its share of the roofline reads low, never high."""
    layers = m["num_hidden_layers"]
    always = (2.0 * (layers * (attention_params(m) + indexer_params(m))
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * layers * router_params(m))
    experts = 2.0 * layers * expert_params(m) * experts_touched(m, counters)
    return always + experts + sparse_attention_bytes(m, counters)


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (the trainer runs one dense block
    repeated, and a selection's training objective is in no key of
    ``config.json``)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up, then down: three stacks of [2048, 768]):
    ``families.grouped_expert_call_cost``. For
    ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    experts = m["num_experts"]
    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_intermediate_size"],
        held=experts, total=experts, up_stacks=2, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the routed
    feed-forward's operations (router and experts), told from the rest of
    a program by the expert axis in a shape they read or write, or by the
    grouped kernel's name. For ``expert_ffn_share.*`` and
    ``prefill_expert_share.*``."""
    e, d, f = m["num_experts"], m["hidden_size"], m["moe_intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the experts' weights
        rf"|{d},{e}"                       # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, E, F], [E, T, F]
        r")\]")
    return lambda text: ("grouped_expert_ffn" in text
                         or shapes.search(text) is not None)
