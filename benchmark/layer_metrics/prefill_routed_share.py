"""Per-layer metric ``prefill_routed_share`` (PR 57): device time of the
routed feed-forward inside the runs of the PREFILL programs, over those
runs' time, by the names the program gives its pieces: the operations whose
compiled instruction lies under ``moe_router``, ``moe_dispatch`` (the sort
of the pairs and the gather of their rows in), ``moe_experts`` (the grouped
kernel's calls, or every expert's matmuls), ``moe_combine`` (the gather of
the rows out and the weighted sum) or ``shared_expert``
(``benchmark/program_scopes.py``: the join). What ``prefill_expert_share``
means and undercounts: a shape predicate sees the kernel's events and not
the rows' way there and back, which carry no expert axis. None with no
recorded map, under ``inside.MIN_SAMPLES`` prefill runs, or where over a
tenth of the prefill runs' own time is unnamed or unjoined
(``program_scopes.HOLE``: the maps are then another tree's)."""

from benchmark import program_scopes


def read(run):
    return program_scopes.prefill_share(run.trace, program_scopes.ROUTED)
