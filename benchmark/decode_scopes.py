"""The decode programs' device time by the program's own names (PR 60):
what ``program_scopes.prefill_share`` reads of the prefill programs, of
the decode programs, for the readers of a layer whose work is told by
scope and not by a kernel's name or an operation's shapes (a layer that
picks its keys among K/V rows: the scores, the top-k, the flags and the
attention are several instructions, some kernels and some not, whatever
implements them)."""

from __future__ import annotations

from benchmark import program_scopes


def decode_share(trace, scopes: tuple):
    """The share of the decode programs' time in operations under one of
    ``scopes``: 0.0 where the maps name nothing so, None without maps or
    runs enough, and None where more than ``program_scopes.HOLE`` percent
    of the decode runs' own time is unnamed or unjoined (the maps are
    then another tree's, and a share of the rest would read low or high
    by as much)."""
    found = program_scopes.summary(trace)
    gap = program_scopes.hole(found["decode"]) if found else None
    if gap is None or gap > program_scopes.HOLE:
        return None
    share = program_scopes.shares(found["decode"])
    return sum(share.get(scope, 0.0) for scope in scopes)
