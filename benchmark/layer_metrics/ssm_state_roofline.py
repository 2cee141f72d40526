"""Per-layer metric ``ssm_state_roofline.*`` (PR 36): the recurrent state
update's share of its roofline. A decode step must read every live slot's
state once and write it once in every layer (``ssm_state_bytes``, the
family's count, from the mean number of live slots); it is memory-bound
(about one multiply-add a byte), so the least time of a step's updates is
those bytes over the chip's bandwidth. The time is that of the operations
that touch the state or the convolution's tail inside the decode programs'
runs (``ssm_op(config)["state"]``: by the state's axes in a shape they read
or write), a step: their share of those runs' time times the runs' time a
step. An update that reads the state twice reads under 67% by that alone.
A family that counts no such bytes gives None."""

from benchmark import experts, flops, inside, systems


def read(run):
    family = systems.family(run.config)
    ssm_op = getattr(family, "ssm_op", None)
    count = getattr(family, "ssm_state_bytes", None)
    if ssm_op is None or count is None:
        return None
    step_ms = inside.decode_program_step_ms(run.trace)
    share = experts.expert_ffn_share(run.trace, ssm_op(run.config)["state"])
    if not step_ms or not share:
        return None
    update_s = share / 100.0 * step_ms * 1e-3
    peak = flops.peaks(run.device["kind"])
    nbytes = count(run.config, run.counters)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / update_s
