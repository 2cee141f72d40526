"""Per-layer metric ``window_bound_share`` (PR 51): of the live slot-steps
of the slice's decode dispatches, the share whose context has passed the
layer plan's window, so that a sliding layer reads its window and not the
context. From the ``engine.dispatch_decode`` spans' own counts (``live``:
the slots that held a request when the chunk was dispatched;
``slots_past_window``: those of them whose first step reads more rows than
the window holds). 100 where every decode step of the cell lies past the
window, which is what ``serve-brief-gen`` is there to measure; a program
that counts none (the parent's, or a model with no sliding layer) gives
None."""

from benchmark import inside, program_spans


def read(run):
    counts = [(s["attrs"]["slots_past_window"], s["attrs"]["live"])
              for s in program_spans.engine_spans() or ()
              if s["name"] == "engine.dispatch_decode"
              and "slots_past_window" in s.get("attrs", {})]
    live = sum(n for _, n in counts)
    if len(counts) < inside.MIN_SAMPLES or live <= 0:
        return None
    return 100.0 * sum(past for past, _ in counts) / live
