"""Per-layer metric ``kv_selected_share.*`` (PR 40): of the rows the live
contexts hold in a full layer (``kv_rows_full``: what a step would read
there with no selection), the share it reads after the indexer's
selection (``kv_rows_selected``: ``min(length, index_topk)`` a slot).
Both from the ``engine.dispatch_decode`` spans' own counts, which the
host takes from its lengths at dispatch. A program that counts no
selection (the parent's, or a model with no indexer) gives None."""

from benchmark import inside, program_spans


def read(run):
    spans = program_spans.engine_spans()
    rows = [(s["attrs"]["kv_rows_full"], s["attrs"]["kv_rows_selected"])
            for s in spans or () if s["name"] == "engine.dispatch_decode"
            and "kv_rows_selected" in s.get("attrs", {})]
    whole = sum(f for f, _ in rows)
    if len(rows) < inside.MIN_SAMPLES or whole <= 0:
        return None
    return 100.0 * sum(s for _, s in rows) / whole
