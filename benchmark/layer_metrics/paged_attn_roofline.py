"""Per-layer metric ``paged_attn_roofline.*`` (PR 33): the paged
decode-attention kernel's share of its roofline. The kernel is
memory-bound (one query token a slot: 2 x heads operations a KV byte pair,
far under the chip's 240 an HBM byte), so the least time of a step's calls
is the bytes of keys and values the step must read, which is the family's
count (``attention_kv_bytes``: a full layer every live token's, a sliding
layer its window's), over the chip's bandwidth. The time is that of the
instructions named ``paged_decode_attn*`` inside the decode programs' runs
(found as PR 30's ``paged_attn_kernel_share`` found them), a step: their share of
those runs' time times the runs' time a step. The kernel fetches whole
pages, and a window that straddles a page's edge one page more, so the
share reads under 100% by that much at the least; a family that counts no
such bytes gives None."""

from benchmark import experts, flops, inside, systems
from benchmark.trace import is_kernel_call

KERNEL = "paged_decode_attn"


def is_kernel(op: str) -> bool:
    return is_kernel_call(op, KERNEL)


def read(run):
    trace = run.trace
    count = getattr(systems.family(run.config), "attention_kv_bytes", None)
    if count is None or trace is None or not trace.devices:
        return None
    calls = sum(1 for n, _, _ in trace.devices[0]["ops"] if is_kernel(n))
    step_ms = inside.decode_program_step_ms(trace)
    share = experts.expert_ffn_share(trace, is_kernel)
    if calls < inside.MIN_SAMPLES or not step_ms or not share:
        return None
    kernel_s = share / 100.0 * step_ms * 1e-3
    peak = flops.peaks(run.device["kind"])
    nbytes = count(run.config, run.counters)
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / kernel_s
