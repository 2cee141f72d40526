"""Per-layer metric ``state_restore_share`` (PR 67): of the rows the
prefill programs computed in the slice (``group`` a dispatch), the share
that BEGAN from the recurrent state a page keeps at its end (a prefix hit
over a plan whose pages keep it) and not from zeros, from the
``engine.dispatch_prefill`` spans' ``state_restores`` and ``group``:
with contexts asked four times, three rows in four; 0 says the mechanism
is off. A program that counts no restores (the parent's; a plan whose
pages keep no state) puts no such count on its spans: None, as under
``inside.MIN_SAMPLES`` dispatches."""

from benchmark import inside, program_spans


def restore_share(spans):
    """A pure function of span records."""
    counted = [s["attrs"] for s in spans or ()
               if s["name"] == "engine.dispatch_prefill"
               and {"group", "state_restores"} <= set(s.get("attrs", {}))]
    rows = sum(a["group"] for a in counted)
    if len(counted) < inside.MIN_SAMPLES or rows <= 0:
        return None
    return 100.0 * sum(a["state_restores"] for a in counted) / rows


def read(run):
    return restore_share(program_spans.engine_spans())
