"""Per-layer metric ``latent_attn_roofline.*`` (PR 40): the latent
attention's share of its roofline in a decode step. The family counts the
operations and the bytes a step's attention and indexer need
(``latent_attention_cost``: absorbed scores and values a row read, the
expansion's halves a slot, the indexer a key scored; the rows and index
keys read and each layer's expansion once); the least time is the larger
of operations over the peak and bytes over the bandwidth (at 128 heads
over a 576-wide row the two meet: 241 operations a byte). The time is
that of ``latent_attn_share``'s operations, a step: their share of the
decode programs' runs times the runs' time a step. Plain XLA gathers the
rows before it reads them (a write and a second read the count does not
have), so the share reads well under 100%. A family that counts no such
cost gives None."""

from benchmark import experts, flops, inside, systems


def read(run):
    family = systems.family(run.config)
    latent_attn_op = getattr(family, "latent_attn_op", None)
    cost = getattr(family, "latent_attention_cost", None)
    if latent_attn_op is None or cost is None:
        return None
    step_ms = inside.decode_program_step_ms(run.trace)
    share = experts.expert_ffn_share(
        run.trace, latent_attn_op(run.config)["attention"])
    if not step_ms or not share:
        return None
    seconds = share / 100.0 * step_ms * 1e-3
    need = cost(run.config, run.counters)
    return flops.roofline_share(need["flops"], need["bytes"], seconds,
                                flops.peaks(run.device["kind"]))[0]
