"""Per-layer metric ``paged_attn_kernel_share.*`` (PR 30): device time of
the paged decode-attention kernel, found by its instruction's name
(``%paged_decode_attn.N = ... custom_call_target="tpu_custom_call"``: the
``name`` of the program's ``pallas_call``), inside the runs of the decode
programs, over those runs' time. The counter that says decode attends
over the pages where they lie: a program without the kernel has no such
instruction, and the reader returns None."""

from benchmark import experts, inside
from benchmark.trace import KERNEL_TARGET

KERNEL = "paged_decode_attn"


def is_kernel(op: str) -> bool:
    return KERNEL_TARGET in op and op.lstrip("%").startswith(KERNEL)


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    calls = sum(1 for n, _, _ in trace.devices[0]["ops"] if is_kernel(n))
    if calls < inside.MIN_SAMPLES:
        return None
    return experts.expert_ffn_share(trace, is_kernel)
