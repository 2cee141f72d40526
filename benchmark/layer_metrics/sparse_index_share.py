"""Per-layer metric ``sparse_index_share`` (PR 60): of the decode
programs' device time under ``attn`` and ``index_select``
(``sparse_attn_share``), the share under ``index_select``: the indexer's
scores over every live index key, the top-k and the flags, against the
attention over the rows they pick. None where ``sparse_attn_share`` is
None or nothing."""

from benchmark import decode_scopes


def read(run):
    whole = decode_scopes.decode_share(run.trace, ("attn", "index_select"))
    index = decode_scopes.decode_share(run.trace, ("index_select",))
    if not whole or index is None:
        return None
    return 100.0 * index / whole
