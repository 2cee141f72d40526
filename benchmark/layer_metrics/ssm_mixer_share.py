"""Per-layer metric ``ssm_mixer_share.*`` (PR 36): device time of the
recurrent mixer's operations (its projections, its convolution and its
state update) inside the runs of the decode programs, over those runs'
time. Which operations are the mixer's is the family's to say, from the
shapes the mixer alone has (``ssm_op(config)["mixer"]``:
``benchmark/families/falcon_h1.py``), as ``expert_ffn_share`` finds a
routed feed-forward's. A family with no such layer, and a program with no
such operation (the parent's), give None."""

from benchmark import experts, systems


def read(run):
    ssm_op = getattr(systems.family(run.config), "ssm_op", None)
    if ssm_op is None:
        return None
    return experts.expert_ffn_share(run.trace, ssm_op(run.config)["mixer"])
