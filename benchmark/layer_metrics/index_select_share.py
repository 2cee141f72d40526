"""Per-layer metric ``index_select_share.*`` (PR 40): of the device time
of the latent attention's operations inside the decode programs' runs
(``latent_attn_share``), the share in the indexer's own: the gather of a
slot's index keys, the index heads' products, the scores and the top-k
(``latent_attn_op(config)["index"]``). A family with no indexer, and a
program with no such operation, give None."""

from benchmark import experts, systems


def read(run):
    latent_attn_op = getattr(systems.family(run.config), "latent_attn_op",
                             None)
    if latent_attn_op is None:
        return None
    ops = latent_attn_op(run.config)
    whole = experts.expert_ffn_share(run.trace, ops["attention"])
    index = experts.expert_ffn_share(run.trace, ops["index"])
    if not whole or index is None:
        return None
    return 100.0 * index / whole
