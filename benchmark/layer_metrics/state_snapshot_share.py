"""Per-layer metric ``state_snapshot_share`` (PR 67): of the prefill
programs' device time, the part under ``state_snapshot`` (the gather of
each row's starting state out of the page before its start, and the
writes of the state at the end of every page the rows complete), by the
program's own names (``benchmark/program_scopes.py``: the join): what it
costs a prefill that a page keeps the recurrent state at its end, which
is what makes the plan's prefix reusable. 0.0 where the maps name
nothing so (a plan whose pages keep no state), None with no recorded
map, under ``inside.MIN_SAMPLES`` prefill runs, or where over a tenth of
the prefill runs' own time is unnamed or unjoined."""

from benchmark import program_scopes

SCOPES = ("state_snapshot",)


def read(run):
    return program_scopes.prefill_share(run.trace, SCOPES)
