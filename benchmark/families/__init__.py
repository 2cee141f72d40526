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
these, under whatever name its reader asks for."""

API = ("model_config", "init_params", "logits", "train_flops_per_token",
       "decode_step_bytes", "flash_train_cost")
