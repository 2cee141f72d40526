"""JaxTrainer: mesh-sharded training harness for the model library.

TPU-native analog of the reference's ``TorchTrainer`` + ``_TorchBackend``
(``train/torch/torch_trainer.py:14``, ``train/torch/config.py:23,149``): where
the reference boots a torch.distributed process group per rank actor and wraps
the model in DDP/FSDP, here the "backend setup" is building a
`jax.sharding.Mesh` and placing one state pytree on it; the train step is one
jit-compiled SPMD program and XLA emits the collectives that DDP/NCCL would
have issued.

The driver-facing surface mirrors the reference: construct with config +
scaling options, call ``fit()``/``train_step()``, receive metrics.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.ops import scopes
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import (
    PRESETS,
    ShardingRules,
    batch_sharding,
    loss_layout,
    tree_shardings,
)
from ray_tpu.train.state import TrainState, state_logical_axes
from ray_tpu.util import program_scopes, tracing


@dataclass
class TrainConfig:
    """Scaling + optimization config (reference: ``ScalingConfig`` +
    framework config, ``air/config.py``)."""

    mesh_axes: dict = field(default_factory=lambda: {"dp": -1})
    strategy: str = "fsdp"          # sharding preset name or ShardingRules
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10000
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    donate_state: bool = True
    # Fused chunked cross-entropy: never materializes the [B, S, vocab]
    # fp32 logits — chunked LM-head matmul + logsumexp in a checkpointed
    # scan. Essential at Llama-3 vocab scale (128k vocab = 8 GB of fp32
    # logits at 8x2048). At a 32k vocab on one v5e the recompute costs
    # 3.4% (19,016 against the dense loss's 19,683 tokens/s/chip at 6 x
    # 2048, d4 widths; PERF.md, PR 39), so it's opt-in. Over a mesh whose
    # batch axes hold several devices it splits the vocabulary over them
    # (loss_vocab_axes): 7,948 tokens/s/chip over {"fsdp": 4} at 16 x
    # 2048, d10 widths, against 6,884 with the loss left to the partitioner.
    fused_loss: bool = False
    loss_chunk: int = 1024
    # Pipeline parallelism (strategy="pp_fsdp"): microbatch count (default
    # = pp size, the minimum that fills the pipeline). The schedule is
    # 1F1B (interleaved fwd/bwd, O(pipeline-depth) activation stash) —
    # autodiff-through-GPipe is NOT offered here because differentiating
    # through the pipelined region with the embedding/head outside trips an
    # XLA partitioner crash on multi-axis meshes (see
    # parallel/pipeline.py); forward-only GPipe remains available via
    # llama_forward_pipelined.
    n_microbatches: int | None = None


class JaxTrainer:
    """Single-controller trainer over one mesh.

    Usage::

        trainer = JaxTrainer(model_cfg, TrainConfig(mesh_axes={"dp":2,"fsdp":2,"tp":2}))
        state = trainer.init_state(jax.random.key(0))
        state, metrics = trainer.train_step(state, batch)  # batch: [B, S+1] tokens
    """

    def __init__(self, model_cfg, cfg: TrainConfig,
                 *, mesh: Mesh | None = None,
                 loss_fn: Callable | None = None):
        """``loss_fn(model_cfg, params, batch) -> scalar`` overrides the
        default next-token cross entropy — the hook that trains
        non-causal objectives (e.g. BERT MLM with a dict batch) through
        the same sharded-state machinery. Batch leaves must share the
        [B, ...] leading axis for data sharding."""
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.loss_fn = loss_fn
        # Model-family dispatch: any module exposing init_params /
        # param_logical_axes / forward over a frozen config dataclass
        # plugs in (llama is the flagship; gpt is the second decoder
        # family). Llama-only features (fused loss, ring attention,
        # 1F1B) are guarded below.
        self.family = self._resolve_family(model_cfg)
        self.mesh = mesh if mesh is not None else create_mesh(cfg.mesh_axes)
        self.rules: ShardingRules = (
            cfg.strategy if isinstance(cfg.strategy, ShardingRules)
            else PRESETS[cfg.strategy]
        )
        self.optimizer = self._make_optimizer()
        self._jit_step = {}
        # Sequence parallelism: use ring attention when the rules shard seq
        # over a mesh axis that actually exists on this mesh.
        sp = self.rules.seq
        self.attn_impl = (
            "ring" if sp is not None and sp in self.mesh.axis_names
            and self.mesh.shape[sp] > 1 else "auto"
        )
        self.sp_axis = sp if self.attn_impl == "ring" else "sp"
        # The mesh axes the fused loss splits the vocabulary over (with
        # the rows' spec beside them), read from the mesh, the rules and
        # the head's shape; () is the plain path, left to the partitioner.
        self.loss_rows, self.loss_vocab_axes = (
            loss_layout(self.mesh, self.rules, model_cfg.vocab_size)
            if cfg.fused_loss else ((), ()))
        # Pipeline parallelism: active when the rules map the stacked-layer
        # dim onto a mesh axis that exists with size > 1.
        ppax = self.rules.layers
        self.pp_axis = (
            ppax if isinstance(ppax, str) and ppax in self.mesh.axis_names
            and self.mesh.shape[ppax] > 1 else None
        )
        if self.family is not llama and (cfg.fused_loss
                                         or self.attn_impl == "ring"):
            raise ValueError(
                "fused_loss / ring attention are llama-only paths")
        if loss_fn is not None and (cfg.fused_loss or self.pp_axis):
            raise ValueError(
                "custom loss_fn cannot combine with fused_loss or "
                "pipeline parallelism (both own the loss computation)")
        # families without a causal-LM `forward` need the loss hook
        if loss_fn is None and not hasattr(self.family, "forward"):
            raise ValueError(
                f"{self.family.__name__} has no causal-LM default; pass "
                "loss_fn= (e.g. wrapping bert.mlm_loss)")
        if self.pp_axis:
            if self.family is not llama:
                raise ValueError(
                    "pipeline parallelism is wired for the llama family "
                    "only (make_llama_stage_fn)")
            n_pp = self.mesh.shape[self.pp_axis]
            if model_cfg.n_layers % n_pp:
                raise ValueError(
                    f"n_layers={model_cfg.n_layers} not divisible by "
                    f"pp={n_pp}"
                )
            if cfg.fused_loss:
                raise ValueError(
                    "fused_loss is redundant under pipeline parallelism: "
                    "the 1F1B loss slot already computes the head "
                    "per-microbatch"
                )

    @staticmethod
    def _resolve_family(model_cfg):
        if isinstance(model_cfg, llama.LlamaConfig):
            return llama
        from ray_tpu.models import bert, gpt

        if isinstance(model_cfg, gpt.GPTConfig):
            return gpt
        if isinstance(model_cfg, bert.BertConfig):
            return bert
        raise TypeError(
            f"unsupported model config {type(model_cfg).__name__}; "
            "expected LlamaConfig, GPTConfig, or BertConfig")

    # --- optimizer (AdamW + cosine schedule + clip, the Llama recipe) ---

    def _make_optimizer(self) -> optax.GradientTransformation:
        c = self.cfg
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=c.learning_rate,
            warmup_steps=c.warmup_steps,
            decay_steps=max(c.total_steps, c.warmup_steps + 1),
            end_value=c.learning_rate * 0.1,
        )
        return optax.chain(
            optax.clip_by_global_norm(c.max_grad_norm),
            optax.adamw(schedule, b1=c.b1, b2=c.b2,
                        weight_decay=c.weight_decay),
        )

    # --- state ---

    def _make_state_fn(self, key):
        params = self.family.init_params(self.model_cfg, key)
        return TrainState.create(params, self.optimizer)

    def _state_axes(self) -> TrainState:
        """Abstract-eval a state skeleton to derive per-leaf logical axes
        (optimizer moments inherit their param's axes — ZeRO-style)."""
        param_axes = self.family.param_logical_axes(self.model_cfg)
        return state_logical_axes(self.abstract_state(), param_axes)

    def _axes_to_sharding(self, ax) -> NamedSharding:
        from ray_tpu.parallel.sharding import logical_sharding

        if ax:
            return logical_sharding(tuple(ax), self.mesh, self.rules)
        return NamedSharding(self.mesh, P())

    def abstract_state(self) -> Any:
        """ShapeDtypeStruct pytree of a TrainState (shared by sharding
        derivation and checkpoint restore)."""
        return jax.eval_shape(self._make_state_fn, jax.random.key(0))

    def state_shardings(self) -> Any:
        """NamedSharding pytree for a TrainState (also used by checkpoint
        restore to place shards directly on devices)."""
        from ray_tpu.parallel.sharding import is_axes_leaf

        return jax.tree.map(
            self._axes_to_sharding, self._state_axes(), is_leaf=is_axes_leaf
        )

    def init_state(self, key) -> TrainState:
        """Initialize params directly INTO their shardings (jit with output
        shardings — each device materializes only its shard; no host-side
        full copy, required for 70B-scale)."""
        return jax.jit(
            self._make_state_fn, out_shardings=self.state_shardings()
        )(key)

    # --- train step ---

    def _loss_fn(self, params, batch, segment_ids=None):
        if self.loss_fn is not None:
            return self.loss_fn(self.model_cfg, params, batch)
        inputs = batch[:, :-1]
        targets = batch[:, 1:]
        mask = (targets != -1).astype(jnp.float32)
        if self.family is not llama:
            logits = self.family.forward(
                self.model_cfg, params, inputs, segment_ids=segment_ids,
                attn_impl=self.attn_impl)
            return llama.cross_entropy_loss(
                logits, jnp.maximum(targets, 0), mask=mask)
        if self.cfg.fused_loss:
            hidden = llama.forward_hidden(
                self.model_cfg, params, inputs, segment_ids=segment_ids,
                attn_impl=self.attn_impl, mesh=self.mesh,
                sp_axis=self.sp_axis)
            return llama.fused_cross_entropy(
                self.model_cfg, params, hidden, targets, mask=mask,
                chunk=self.cfg.loss_chunk, mesh=self.mesh,
                rows=self.loss_rows, vocab_axes=self.loss_vocab_axes)
        logits = llama.forward(self.model_cfg, params, inputs,
                               segment_ids=segment_ids,
                               attn_impl=self.attn_impl,
                               mesh=self.mesh, sp_axis=self.sp_axis)
        loss = llama.cross_entropy_loss(
            logits, jnp.maximum(targets, 0), mask=mask
        )
        return loss

    def _pp_loss_and_grad(self, params, batch):
        """1F1B pipelined loss + grads (pipeline_value_and_grad implements
        the backward itself — this is NOT differentiated through)."""
        from ray_tpu.ops.rope import rope_sin_cos
        from ray_tpu.parallel.pipeline import (
            make_llama_head_fn,
            make_llama_stage_fn,
            pipeline_value_and_grad,
            split_stages,
        )

        cfg = self.model_cfg
        n_pp = self.mesh.shape[self.pp_axis]
        m = self.cfg.n_microbatches or n_pp
        inputs = batch[:, :-1]
        targets = batch[:, 1:]
        mask = (targets != -1).astype(jnp.float32)
        b, s = inputs.shape
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")

        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        sin, cos = rope_sin_cos(positions, cfg.head_dim, theta=cfg.rope_theta)
        stage_fn = make_llama_stage_fn(cfg, sin, cos, self.attn_impl)
        head_fn = make_llama_head_fn(cfg)
        # io params: embedding (stage-0 lookup, + head when tied), final
        # norm + head (last stage). The schedule accumulates ALL their grad
        # contributions into one d_io — tied embeddings need no fixup.
        io_params = {k: v for k, v in params.items() if k != "blocks"}

        def embed_fn(io, tok):
            return io["embedding"][tok]

        mb = b // m
        (loss_sum, weight_sum), (d_sp, d_io, _) = pipeline_value_and_grad(
            stage_fn, head_fn,
            split_stages(params["blocks"], n_pp), io_params,
            inputs.reshape(m, mb, s),
            targets.reshape(m, mb, s),
            mask.reshape(m, mb, s),
            mesh=self.mesh, axis=self.pp_axis,
            embed_fn=embed_fn,
        )
        weight = jnp.maximum(weight_sum, 1.0)
        grads = dict(
            d_io,
            blocks=jax.tree.map(
                lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
                d_sp),
        )
        # grads are of loss_sum; mean-loss grads = grads / Σmask
        grads = jax.tree.map(
            lambda g, p: (g / weight).astype(p.dtype), grads, params)
        return loss_sum / weight, grads

    def _step(self, state: TrainState, batch):
        if self.pp_axis:
            loss, grads = self._pp_loss_and_grad(state.params, batch)
        else:
            loss, grads = jax.value_and_grad(self._loss_fn)(
                state.params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, new_opt = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1
        )
        metrics = {"loss": loss, "grad_norm": gnorm, "step": new_state.step}
        return new_state, metrics

    def _batch_shardings(self, batch):
        """Per-leaf data sharding: dim 0 is the batch axis, the rest
        replicated — so dict batches may mix ranks (e.g. [B, S] tokens
        with [B] labels)."""
        def leaf(x):
            nd = int(getattr(x, "ndim", 0))
            if nd == 0:   # python scalars / 0-d arrays: replicate
                return NamedSharding(self.mesh, P())
            return batch_sharding(self.mesh, self.rules, ndim=nd,
                                  shard_seq=False)

        return jax.tree.map(leaf, batch)

    def compile_step(self, state: TrainState, batch):
        # keyed on the batch pytree structure + leaf ranks: a later
        # batch with a different structure gets its own jit rather than
        # hitting stale in_shardings
        key = (jax.tree.structure(batch),
               tuple(int(getattr(x, "ndim", 0))
                     for x in jax.tree.leaves(batch)))
        step = self._jit_step.get(key)
        if step is None:
            donate = (0,) if self.cfg.donate_state else ()
            # the layout is chosen as the step is traced, once a compiled
            # step: the span states it where a counter could only say 1
            with tracing.phase("train.compile_step", kind="train", attrs={
                    "loss_vocab_axes": list(self.loss_vocab_axes),
                    "loss_vocab_shards": math.prod(
                        self.mesh.shape[a] for a in self.loss_vocab_axes)}):
                step = jax.jit(
                    self._step,
                    # state keeps its shardings
                    in_shardings=(None, self._batch_shardings(batch)),
                    donate_argnums=donate,
                )
            self._jit_step[key] = step
        return step

    def train_step(self, state: TrainState, batch):
        """One SPMD optimization step. ``batch``: int32 [B, S+1] tokens
        (last column is the shifted target; -1 = padding), or — with a
        custom ``loss_fn`` — any pytree whose leaves lead with the
        batch dim."""
        step_fn = self.compile_step(state, batch)
        batch = jax.device_put(batch, self._batch_shardings(batch))
        return step_fn(state, batch)

    # --- simple fit loop (full harness arrives with the trial controller) ---

    def fit(self, state: TrainState, data_iter, *, steps: int,
            log_every: int = 10, callback: Callable | None = None):
        history = []
        t0 = time.perf_counter()
        traced = False      # whether a step ran while spans were recorded
        for i in range(steps):
            batch = next(data_iter)
            traced = traced or tracing.recording()
            state, metrics = self.train_step(state, batch)
            if (i + 1) % log_every == 0 or i == steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["steps_per_s"] = (i + 1) / (time.perf_counter() - t0)
                history.append(m)
                if callback:
                    callback(m)
        if traced:
            # what the step's instructions are pieces of, once the last
            # step is behind (util/program_scopes.py)
            program_scopes.record_programs({"jit_" + self._step.__name__})
        return state, history
