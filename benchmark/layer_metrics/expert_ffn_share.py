"""Per-layer metric ``expert_ffn_share.*`` (see benchmark/experts.py)."""

from benchmark import experts, systems


def read(run):
    is_expert_op = getattr(systems.family(run.config), "expert_ffn_op", None)
    if is_expert_op is None:
        return None
    return experts.expert_ffn_share(run.trace, is_expert_op(run.config))
