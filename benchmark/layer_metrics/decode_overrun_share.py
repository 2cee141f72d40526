"""Per-layer metric ``decode_overrun_share.*`` (PR 38): of the slot-steps
of slots that held a request when their chunk was dispatched, the share
that decodes past an answer's end (inside the answer's last chunk, and
the whole chunk in flight behind it), from the chunks' ``engine.emit``
spans (``benchmark/dispatch_account.py``). A program that keeps no such
account (the parent's) gives None."""

from benchmark import dispatch_account, program_spans


def read(run):
    return dispatch_account.decode_overrun_share(
        program_spans.engine_spans())
