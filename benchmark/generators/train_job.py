"""A pre-training job: seeded token batches made on the device, a new
batch each step. The batch size is the configuration's (the largest its
chip layout accepts); the sequence length is the job's."""

from __future__ import annotations


def build(p: dict, model: dict, system: dict, seed: int, seconds: float):
    import jax
    import jax.numpy as jnp

    batch, seq, vocab = system["batch_sequences"], p["seq_len"], \
        model["vocab_size"]

    def make_batch(key, step):
        return jax.random.randint(jax.random.fold_in(key, step),
                                  (batch, seq + 1), 0, vocab, jnp.int32)

    return {"make_batch": make_batch, "batch": batch, "seq_len": seq}
