"""Per-layer metric ``prefill_attn_share`` (PR 57): of the prefill programs'
device time, the part under ``attn`` (attention over K/V pages, the prefill
kernel or the plain path, sliding and full), ``latent_attn`` (attention
over latent rows) and ``index_select`` (an indexer's scores and top-k), by
the program's own names (``benchmark/program_scopes.py``: the join). None
with no recorded map, under ``inside.MIN_SAMPLES`` prefill runs, or where
over a tenth of the prefill runs' own time is unnamed or unjoined
(``program_scopes.HOLE``: the maps are then another tree's)."""

from benchmark import program_scopes


def read(run):
    return program_scopes.prefill_share(run.trace, program_scopes.ATTENTION)
