"""Per-layer metric ``sparse_attn_share`` (PR 60): of the decode programs'
device time, the part under ``attn`` (attention over K/V pages, kernel or
plain) and ``index_select`` (an indexer's scores, its top-k and the flags
made of them), by the program's own names (``benchmark/decode_scopes.py``):
what a layer that picks its keys among K/V rows costs a decode step,
whatever implements it. None with no recorded map, under
``inside.MIN_SAMPLES`` decode runs, or where over a tenth of the decode
runs' own time is unnamed or unjoined."""

from benchmark import decode_scopes

SCOPES = ("attn", "index_select")


def read(run):
    return decode_scopes.decode_share(run.trace, SCOPES)
