"""Per-layer metric ``prefill_scan_share.*`` (PR 36): device time of the
recurrent mixer's operations (the chunked scan over the prompt, its
convolution and projections) inside the runs of the PREFILL programs,
over those runs' time: what a prompt's pass through the mixer costs
beside its attention and its feed-forward. The operations are the
family's to name (``ssm_op(config)["mixer"]``); a loop or a branch is left
out, its event spans its body's operations. None where the family has no
such layer, the program no such operation, or the slice fewer than
``inside.MIN_SAMPLES`` prefill runs."""

from benchmark import inside, systems
from benchmark.trace import CONTAINERS, opcode


def read(run):
    ssm_op = getattr(systems.family(run.config), "ssm_op", None)
    trace = run.trace
    if ssm_op is None or trace is None or not trace.devices:
        return None
    is_mixer = ssm_op(run.config)["mixer"]
    dev = trace.devices[0]
    runs = sorted((s, e) for n, s, e in dev["modules"]
                  if inside.PREFILL.match(n))
    total = sum(e - s for s, e in runs)
    if len(runs) < inside.MIN_SAMPLES or total <= 0:
        return None
    seconds, i = 0.0, 0
    for name, s, e in sorted(dev["ops"], key=lambda x: x[1]):
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs):
            break
        if (s >= runs[i][0] and opcode(name) not in CONTAINERS
                and is_mixer(name)):
            seconds += min(e, runs[i][1]) - s
    return 100.0 * seconds / total if seconds else None
