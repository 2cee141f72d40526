"""Reader ``grouped_expert_ffn_roofline`` (PR 49). NOT listed in
``BENCHMARK.json``: on the chip it read 74.9 / 95.5 / 143 / 178% in the
four expert cells, since the family counts the weights of the experts an
EVEN routing touches and the seeded routers are far from even (a decode
step of 128 tokens reaches 22 of 64 held experts in ``serve-reason-gen``);
it can be listed once the program records what a prefill's dispatch
touched (PERF.md, section 7). What it reads: the grouped
expert kernel's share of its roofline, in the prefill programs that hold
it (PR 44: ``ops/grouped_expert_ffn.py``, two calls a sparse layer, the up
projections with the activation and the down projection; a prefill of few
rows computes every held expert for every row and holds no such kernel).

The kernel's calls are the instructions named ``grouped_expert_ffn*``
inside the prefill programs' runs. A call's result is ``[pair rows, n]``,
read from the instruction's own text: the rows are the dispatch's
token-rows times the experts a token picks (``num_experts_per_tok``, the
published key), ``n`` tells the up call from the down call. Of the
token-rows only the prompt tokens are routed (padding goes to no expert):
their share is the slice's own, from the ``engine.dispatch_prefill`` spans
of that many token-rows (``dispatch_account.prefill_fill_by_rows``); of
their choices only those on an expert held here reach the kernel: the
program's own count over the slice's decode chunks (``routed_here_share``)
where it keeps one, else the family's. What a call must do for those
pairs, operations and bytes, is the family's count
(``grouped_expert_cost``); the least time is the larger of operations over
the chip's peak and bytes over its bandwidth, summed over the calls, over
the calls' time. A call whose dispatch no span of the slice describes is
left out, time and all. None where the family has no such count, the
program no such kernel, or the slice fewer than ``inside.MIN_SAMPLES``
calls."""

from benchmark import (dispatch_account, experts, flops, inside,
                       program_spans, systems)
from benchmark.trace import is_kernel_call, result_dims

KERNEL = "grouped_expert_ffn"


def is_kernel(op: str) -> bool:
    return is_kernel_call(op, KERNEL)


def read(run):
    trace = run.trace
    cost = getattr(systems.family(run.config), "grouped_expert_cost", None)
    if cost is None or trace is None or not trace.devices:
        return None
    spans = program_spans.engine_spans()
    fill = dispatch_account.prefill_fill_by_rows(spans)
    here = experts.chunk_stat_mean(spans, "routed_here_share")
    top_k = run.config["num_experts_per_tok"]
    peak = flops.peaks(run.device["kind"])
    calls, least, seconds = 0, 0.0, 0.0
    for op, took in experts.ops_inside(trace, inside.PREFILL, is_kernel)[0]:
        dims = result_dims(op)           # [pair rows, n]
        if len(dims) != 2 or dims[0] // top_k not in fill:
            continue
        need = cost(run.config, dims[1],
                    dims[0] * fill[dims[0] // top_k], here)
        if need is None:
            continue
        calls += 1
        seconds += took
        least += max(need["flops"] / peak["bf16_flops_per_s"],
                     need["bytes"] / peak["hbm_bytes_per_s"])
    if calls < inside.MIN_SAMPLES or seconds <= 0:
        return None
    return 100.0 * least / seconds
