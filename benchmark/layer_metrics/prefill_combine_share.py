"""Per-layer metric ``prefill_combine_share`` (PR 57): of the prefill
programs' device time, the part under ``moe_dispatch`` and ``moe_combine``:
the sort of the (token, choice) pairs, the gather of their rows in, the
gather of the experts' float32 rows out and their weighted sum. The rows'
way round the grouped expert kernel, which no operation's shape tells from
any other gather (``benchmark/program_scopes.py``: the join by the
program's own names). None with no recorded map, under
``inside.MIN_SAMPLES`` prefill runs, or where over a tenth of the prefill
runs' own time is unnamed or unjoined (``program_scopes.HOLE``: the maps
are then another tree's)."""

from benchmark import program_scopes


def read(run):
    return program_scopes.prefill_share(run.trace, program_scopes.COMBINE)
