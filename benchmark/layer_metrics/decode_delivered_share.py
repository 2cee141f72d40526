"""Per-layer metric ``decode_delivered_share.*`` (PR 38): of the slot-steps
the decode programs computed in the slice (``chunk x max_batch`` a
dispatch), the share that is a token on a request's stream, from the
chunks' ``engine.emit`` spans (``benchmark/dispatch_account.py``). A
program that keeps no such account (the parent's) gives None."""

from benchmark import dispatch_account, program_spans


def read(run):
    return dispatch_account.decode_delivered_share(
        program_spans.engine_spans())
