"""The one vocabulary of ``jax.named_scope`` names the device programs
carry: what an instruction of a compiled program, and so an operation's
event in a device trace, is a piece OF. A scope is metadata of the lowered
program (no operation, no argument, no sync, and no character of its
lowered text), so every ``with jax.named_scope(...)`` is always there, in
the function that does the work (the op, or the model's block where an op
is shared). An instruction's scope is the INNERMOST of these names on its
``op_name`` path (``util/program_scopes.py:scope_of``), so whoever sums a
parent counts its children with it. docs/tracing_plane.md section 1a."""

EMBED = "embed"                  # token ids -> the stream's start
NORM = "norm"                    # an RMS or layer norm (``ops/norms.py``)
ATTN_QKV = "attn_qkv"            # q/k/v or latent projections, rotary
KV_WRITE = "kv_write"            # new K/V or latent rows into their pages
ATTN = "attn"                    # attention over K/V, kernel or plain
ATTN_OUT = "attn_out"            # the heads' gate and ``wo``
LATENT_ATTN = "latent_attn"      # attention over latent rows
INDEX_SELECT = "index_select"    # the indexer's scores and its top-k
FFN = "ffn"                      # a dense feed-forward
MOE_ROUTER = "moe_router"        # router scores, the choice, the load
MOE_DISPATCH = "moe_dispatch"    # the pairs' sort, the gather of rows in
MOE_EXPERTS = "moe_experts"      # the grouped kernel or every expert
MOE_COMBINE = "moe_combine"      # the gather of rows out, the weighted sum
SHARED_EXPERT = "shared_expert"  # the expert every token takes
SSM_MIXER = "ssm_mixer"          # a state-space mixer, end to end
SSM_SCAN = "ssm_scan"            # inside it: the chunked scan of a prompt
SSM_STEP = "ssm_step"            # inside it: one token's state update
SHORT_CONV = "short_conv"        # inside it: a gated short convolution whole
STATE_SNAPSHOT = "state_snapshot"  # a state read from, written to its pages
LM_HEAD = "lm_head"              # the last rows and the head's logits
SAMPLE = "sample"                # tokens from logits, and their keeping
LOSS = "loss"                    # the train step's cross-entropy
OPTIMIZER = "optimizer"          # the train step's parameter update

VOCABULARY = (EMBED, NORM, ATTN_QKV, KV_WRITE, ATTN, ATTN_OUT, LATENT_ATTN,
              INDEX_SELECT, FFN, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS,
              MOE_COMBINE, SHARED_EXPERT, SSM_MIXER, SSM_SCAN, SSM_STEP,
              SHORT_CONV, STATE_SNAPSHOT, LM_HEAD, SAMPLE, LOSS, OPTIMIZER)
