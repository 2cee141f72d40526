"""Model families: everything the benchmark knows about a model's block.

A configuration file names its family under ``family``; the family is the
one module ``benchmark/families/<family>.py``, found by that name and by
nothing else (``systems.family(config)``), and the rest of the benchmark
never names a family or a block's insides. A new family is a new module
here; no file that is there changes. The module has every name of ``API``
(``config`` is the configuration file as read: published keys at its top
level, the program's settings under ``system``):

the adapter, the one part that touches the program (imports of
``ray_tpu`` inside the two functions, never at the module's top):

- ``model_config(config)``: the program's config object;
- ``init_params(model_cfg, key)``: weights from a PRNG key, in the type
  they are served in (``systems.make_params`` jits it);

the plain reference, which decides ``correct`` and imports nothing from
the program:

- ``logits(config, params, tokens)``: float32 logits [b, s, vocab] of
  ``tokens`` [b, s] in straightforward ``jax.numpy`` under
  ``jax.default_matmul_precision("highest")``, reading the program's
  parameter layout (data, not code). The comparisons built on it are
  generic and written once: ``benchmark/reference.py``;

the counts, from shapes alone. One that the family has nothing to count
for returns None: the reader then returns None and the harness leaves the
metric out of the line:

- ``train_flops_per_token(config, seq_len)``: operations of a train step
  a token, forward and backward, recomputation not counted (``train_mfu``);
- ``decode_step_bytes(config, counters)``: HBM bytes one decode step must
  move, given the run's counters (``live_kv_tokens_mean`` today; a sparse
  family wants more of them, so it gets them all) (``decode_roofline.*``);
- ``flash_train_cost(config, batch, seq_len)``: ``{"flops", "bytes"}`` of
  the attention kernels of one train step on one chip (``flash_roofline``).

A family with a kernel or a layer of another kind brings a reader of its
own (``benchmark/layer_metrics/<metric>.py``) and keeps that count beside
these, under whatever name its reader asks for. Two such counts are asked
for by readers that several families share, and a family that has the
layer has them under these names: ``attention_kv_bytes(config, counters)``
(``paged_attn_roofline``) and ``grouped_expert_cost(config, n_out, pairs,
here_share)`` (``grouped_expert_ffn_roofline``; its arithmetic is
``grouped_expert_call_cost`` below, the family says the widths)."""

API = ("model_config", "init_params", "logits", "train_flops_per_token",
       "decode_step_bytes", "flash_train_cost")


def grouped_matmul_cost(held: int, k: int, n: int, stacks: int,
                        pairs: float, itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` one call of a grouped matmul must do: ``pairs``
    rows, each through its own expert's ``stacks`` matrices [k, n] of the
    ``held`` experts' stacks. Operations: 2 a multiply-add, for the rows
    the experts really got. Bytes: the weights of the experts that got a
    row, once, and nothing else (the rows themselves are a hundredth of
    that at the widths the benchmark has): of ``held`` experts, ``pairs``
    rows spread evenly reach ``held x (1 - (1 - 1 / held) ** pairs)``.
    A routing less even than that reaches fewer, so the share reads a
    little high there; with 500 rows or more on 64 experts it is all of
    them either way."""
    touched = held * (1.0 - (1.0 - 1.0 / held) ** pairs)
    return {"flops": 2.0 * pairs * k * n * stacks,
            "bytes": touched * stacks * k * n * itemsize}


def grouped_expert_call_cost(*, hidden: int, width: int, held: int,
                             total: int, up_stacks: int, n_out: int,
                             pairs: float, here_share=None):
    """What one call of the grouped expert kernel (``grouped_expert_ffn``
    in a trace: PR 44) whose result is ``n_out`` wide must do for a
    dispatch whose prompt tokens made ``pairs`` (token, choice) pairs over
    ALL ``total`` experts of the router, ``here_share`` of them on one of
    the ``held`` experts (the program's own count; None: the held share
    of an even routing). The call is the up projection (``up_stacks``
    matrices [hidden, width]: gate and up, or up alone) with the
    activation, result ``width`` wide, or the down projection [width,
    hidden]; None for another width."""
    if here_share is None:
        here_share = held / total
    if n_out == width:
        k, n, stacks = hidden, width, up_stacks
    elif n_out == hidden:
        k, n, stacks = width, hidden, 1
    else:
        return None
    return grouped_matmul_cost(held, k, n, stacks, pairs * here_share)
