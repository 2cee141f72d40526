"""Per-layer metric ``prefill_expert_share.*`` (PR 43): device time of the
routed experts' operations (the router and the held experts' matmuls,
whichever formulation the program's token count picked) inside the runs of
the PREFILL programs, over those runs' time: what a prompt's pass through
the experts costs beside its mixers and its attention. A prefill of
``DENSE_MAX_TOKENS`` rows or fewer computes EVERY held expert for every
row, so this share is where a grouped expert kernel would show. The
operations are the family's to name (``expert_ffn_op(config)``, as
``expert_ffn_share.*`` reads the decode programs'); a loop or a branch is
left out, its event spans its body's operations. None where the family has
no such layer, the program no such operation, or the slice fewer than
``inside.MIN_SAMPLES`` prefill runs."""

from benchmark import experts, inside, systems


def read(run):
    expert_op = getattr(systems.family(run.config), "expert_ffn_op", None)
    trace = run.trace
    if expert_op is None or trace is None or not trace.devices:
        return None
    found, runs, total = experts.ops_inside(trace, inside.PREFILL,
                                            expert_op(run.config))
    if runs < inside.MIN_SAMPLES or total <= 0:
        return None
    seconds = sum(s for _, s in found)
    return 100.0 * seconds / total if seconds else None
