"""Per-layer metric ``kv_window_read_share.*`` (PR 33): of the KV rows a
decode step would read if every layer attended over its whole context,
the share it does read with the sliding layers held to their window. From
the ``engine.dispatch_decode`` spans' own counts (``kv_rows_full``: the
rows the chunk's first step reads in a full layer, over the live slots;
``kv_rows_window``: in a sliding layer) and the family's count of the two
kinds of layer. A program that counts no window (the parent's, or a model
with no sliding layer) gives None."""

from benchmark import inside, program_spans, systems


def read(run):
    counts = getattr(systems.family(run.config), "attention_layer_counts",
                     None)
    spans = program_spans.engine_spans()
    if counts is None or not spans:
        return None
    full, sliding = counts(run.config)
    rows = [(s["attrs"]["kv_rows_full"], s["attrs"]["kv_rows_window"])
            for s in spans if s["name"] == "engine.dispatch_decode"
            and "kv_rows_window" in s.get("attrs", {})]
    whole = sum(f for f, _ in rows) * (full + sliding)
    if len(rows) < inside.MIN_SAMPLES or whole <= 0:
        return None
    read_rows = sum(f * full + w * sliding for f, w in rows)
    return 100.0 * read_rows / whole
