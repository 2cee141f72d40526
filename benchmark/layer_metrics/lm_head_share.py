"""Per-layer metric ``lm_head_share`` (PR 62): of the decode programs'
device time, the part under ``lm_head`` (the last rows' logits), by the
program's own names (``benchmark/decode_scopes.py``), for a head TIED to
the embedding (``tie_word_embeddings``): such a head reads the embedding
[vocab, d] where it lies, or a decode step copies a transpose of it
first, as many bytes again in and out, and the share says which (by
bytes the head is ``d x vocab x 2`` of a step's: ``decode_step_bytes``).
None for an untied head, with no recorded map, under
``inside.MIN_SAMPLES`` decode runs, or where over a tenth of the decode
runs' own time is unnamed or unjoined."""

from benchmark import decode_scopes

SCOPES = ("lm_head",)


def read(run):
    if not run.config.get("tie_word_embeddings"):
        return None
    return decode_scopes.decode_share(run.trace, SCOPES)
