"""Per-layer metric ``sparse_attn_roofline`` (PR 60): the share of its
roofline that a decode step's attention with a learned selection over K/V
rows reaches. Memory-bound (one query a slot: 2 x heads operations a KV
byte pair, and 2 x index heads an index key's byte, far under the chip's
240 an HBM byte), so the least time is the family's count of the bytes
the step's attention and selection must read (``sparse_attention_bytes``:
a slot's ``min(length, topk)`` K and V rows and every live index key, over
the layers) over the chip's bandwidth. The time is that of the decode
programs' operations under ``attn`` and ``index_select``, a step: their
share of the decode runs' time (``sparse_attn_share``) times the runs'
time a step. A form that reads the rows it does not pick, or gathers
before it reads, reads low: that is the finding. A family that counts no
such bytes gives None."""

from benchmark import decode_scopes, flops, inside, systems


def read(run):
    count = getattr(systems.family(run.config), "sparse_attention_bytes",
                    None)
    if count is None:
        return None
    step_ms = inside.decode_program_step_ms(run.trace)
    share = decode_scopes.decode_share(run.trace, ("attn", "index_select"))
    if not step_ms or not share:
        return None
    seconds = share / 100.0 * step_ms * 1e-3
    peak = flops.peaks(run.device["kind"])
    return (100.0 * count(run.config, run.counters)
            / peak["hbm_bytes_per_s"] / seconds)
