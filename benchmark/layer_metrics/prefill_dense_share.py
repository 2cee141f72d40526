"""Per-layer metric ``prefill_dense_share`` (PR 57): of the prefill programs'
device time, the part under ``attn_qkv`` (the q/k/v or latent projections
and the rotary), ``attn_out`` (the heads' gate and ``wo``), ``ffn`` (a
dense feed-forward) and ``lm_head``: the matmuls over the dispatch's
``group x bucket`` token-rows, padding included (beside
``prefill_fill_share``, which says how many of those rows are tokens), by
the program's own names (``benchmark/program_scopes.py``: the join). None
with no recorded map, under ``inside.MIN_SAMPLES`` prefill runs, or where
over a tenth of the prefill runs' own time is unnamed or unjoined
(``program_scopes.HOLE``: the maps are then another tree's)."""

from benchmark import program_scopes


def read(run):
    return program_scopes.prefill_share(run.trace, program_scopes.DENSE)
