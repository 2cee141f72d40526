"""Per-layer metric ``latent_attn_share.*`` (PR 40): device time of the
latent attention's and its indexer's operations (the gathers of rows and
index keys, the absorbed scores, softmax and values, the indexer's scores
and its top-k) inside the runs of the decode programs, over those runs'
time. Which operations those are is the family's to say, from the shapes
they alone have (``latent_attn_op(config)["attention"]``:
``benchmark/families/dots3_note.py``), as ``expert_ffn_share`` finds a
routed feed-forward's. A family with no such layer, and a program with no
such operation (the parent's), give None."""

from benchmark import experts, systems


def read(run):
    latent_attn_op = getattr(systems.family(run.config), "latent_attn_op",
                             None)
    if latent_attn_op is None:
        return None
    return experts.expert_ffn_share(
        run.trace, latent_attn_op(run.config)["attention"])
