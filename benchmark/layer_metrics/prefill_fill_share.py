"""Per-layer metric ``prefill_fill_share.*`` (PR 38): of the token-rows
the prefill programs computed in the slice (``group x bucket`` a
dispatch, the bucket a power of two), the share that is a prompt token,
from the ``engine.dispatch_prefill`` spans' ``group``, ``bucket`` and
``new_tokens`` (``benchmark/dispatch_account.py``); the rest is
padding."""

from benchmark import dispatch_account, program_spans


def read(run):
    return dispatch_account.prefill_fill_share(program_spans.engine_spans())
