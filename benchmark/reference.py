"""The comparisons with the plain reference that decide ``correct``,
written once for every model family: the next-token loss of a batch and a
served answer teacher-forced through the reference. The reference itself
is the family's (``logits(config, params, tokens)`` of
``benchmark/families/<family>.py``: float32 ``jax.numpy`` at ``highest``
matmul precision, no kernels, no cache, no batching tricks and nothing
imported from the program); a caller hands it in, as ``systems.family(
config).logits``. The limits the two numbers are held to stand with
their callers (``LOSS_TOL`` in ``runners/train.py``, ``TOKEN_GAP_TOL`` in
``serving.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _nll_sum(lg, targets):
    lse = jax.nn.logsumexp(lg, axis=-1)
    tl = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tl)


def loss(logits, config: dict, params: dict, batch,
         rows_per_call: int = 1) -> float:
    """Mean next-token cross entropy of ``batch`` [B, S+1] under the
    family's ``logits``, a few rows at a time (float32 logits of a whole
    batch would not fit beside a train state)."""
    total, count = 0.0, 0
    for i in range(0, batch.shape[0], rows_per_call):
        rows = batch[i:i + rows_per_call]
        lg = logits(config, params, rows[:, :-1])
        total += float(_nll_sum(lg, rows[:, 1:]))
        count += rows.shape[0] * (rows.shape[1] - 1)
    return total / count


def token_gap(logits, config: dict, params: dict, prompt, tokens) -> tuple:
    """Teacher-force a served answer through the family's ``logits``: (the
    largest amount by which the reference's logit of a token the system
    chose falls short of the reference's best logit at that position, how
    many tokens are not the reference's own greedy choice). (0.0, 0) when
    every token is."""
    seq = jnp.concatenate([jnp.asarray(prompt, jnp.int32),
                           jnp.asarray(tokens[:-1], jnp.int32)])[None]
    lg = logits(config, params, seq)[0, len(prompt) - 1:]
    chosen = jnp.take_along_axis(
        lg, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    short = lg.max(axis=1) - chosen
    return float(jnp.max(short)), int(jnp.sum(short > 0))
