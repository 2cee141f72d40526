"""Arithmetic of the per-layer metrics that say how much of what the
engine's two programs compute reaches a client (PR 38), from the counts
the engine loop puts on its own spans (``program_spans``). A decode
program computes ``chunk x max_batch`` slot-steps a dispatch whatever the
slots hold; the chunk's ``engine.emit`` span says what became of them
where the chunk was read back: ``tokens`` delivered to a request's stream,
``overrun_tail`` (a live slot's steps after its answer ended inside the
chunk), ``overrun_ahead`` (all of a live slot's steps where its answer had
ended before the chunk was read: the double buffer's price) and ``vacant``
(slots not live at dispatch), which sum to ``slot_steps``. A prefill
program computes ``group x bucket`` token-rows a dispatch, of which
``new_tokens`` are prompt tokens and the rest padding to the power-of-two
bucket (``engine.dispatch_prefill``). Pure functions of a list of span
records, so the CPU tests run them on synthetic ones; each returns None
where there is nothing to read (a program from before PR 38 records no
decode account), or fewer than ``inside.MIN_SAMPLES`` samples."""

from __future__ import annotations

from benchmark import inside


def _chunk_accounts(spans) -> list:
    """The ``attrs`` of the slice's chunk emissions that carry the
    account."""
    return [s["attrs"] for s in spans or ()
            if s["name"] == "engine.emit"
            and s.get("attrs", {}).get("what") == "chunk"
            and "slot_steps" in s["attrs"]]


def decode_delivered_share(spans):
    """Of the slot-steps the decode programs computed, the share that is
    a token on a request's stream."""
    chunks = _chunk_accounts(spans)
    steps = sum(a["slot_steps"] for a in chunks)
    if len(chunks) < inside.MIN_SAMPLES or steps <= 0:
        return None
    return 100.0 * sum(a["tokens"] for a in chunks) / steps


def decode_overrun_share(spans):
    """Of the LIVE slot-steps (the slots that held a request at dispatch),
    the share that decodes past an answer's end."""
    chunks = _chunk_accounts(spans)
    live = sum(a["slot_steps"] - a["vacant"] for a in chunks)
    if len(chunks) < inside.MIN_SAMPLES or live <= 0:
        return None
    return 100.0 * sum(a["overrun_tail"] + a["overrun_ahead"]
                       for a in chunks) / live


def _prefill_dispatches(spans) -> list:
    return [s["attrs"] for s in spans or ()
            if s["name"] == "engine.dispatch_prefill"
            and {"group", "bucket", "new_tokens"} <= set(
                s.get("attrs", {}))]


def prefill_fill_by_rows(spans) -> dict:
    """{token-rows of a dispatch: the share of them that is a prompt
    token, over the slice's dispatches of that many rows}: what a reader
    needs that knows a program's run by its rows alone."""
    new, rows = {}, {}
    for a in _prefill_dispatches(spans):
        n = a.get("token_rows", a["group"] * a["bucket"])
        new[n] = new.get(n, 0) + a["new_tokens"]
        rows[n] = rows.get(n, 0) + n
    return {n: new[n] / rows[n] for n in rows if rows[n] > 0}


def prefill_fill_share(spans):
    """Of the token-rows the prefill programs computed (``group x
    bucket`` a dispatch: the span's ``token_rows``, which a program from
    before PR 38 leaves to be multiplied out), the share that is a prompt
    token."""
    dispatches = _prefill_dispatches(spans)
    rows = sum(a.get("token_rows", a["group"] * a["bucket"])
               for a in dispatches)
    if len(dispatches) < inside.MIN_SAMPLES or rows <= 0:
        return None
    return 100.0 * sum(a["new_tokens"] for a in dispatches) / rows
