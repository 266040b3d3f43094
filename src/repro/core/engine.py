"""Compiled step functions — including the paper's core mechanism as a
single XLA program: ``combined_step`` executes a LoRA training step AND
an inference batch over ONE shared copy of the base weights (DESIGN.md
§2: the TPU-native form of CoLLM's model-sharing / spatial multiplexing).

All steps take and return explicit pytrees so they jit/pjit cleanly and
the dry-run can lower them with ShapeDtypeStructs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import Model, build
from repro.optim.adamw import AdamW, AdamWState, global_norm


@dataclasses.dataclass(frozen=True)
class Engine:
    """Step factory for one architecture."""
    model: Model
    optimizer: AdamW

    # ----------------------------------------------------------- training --
    def train_step(self, params: Any, lora: Any, opt_state: AdamWState,
                   batch: Any,
                   *, skip_masked_blocks: bool = False,
                   ce_chunk: int = 512, grad_accum: int = 1,
                   train_tokens: int = 0
                   ) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
        """LoRA-only gradient step: base weights frozen (PEFT).

        ``grad_accum`` > 1 splits the global batch into microbatches
        scanned sequentially with f32 gradient accumulation — the
        standard memory lever for the large train cells (activations
        live per-microbatch; LoRA grads are tiny so the accumulator is
        nearly free).  The per-microbatch |g|² is also what the
        gradient-noise-scale estimator (Eq. 8's p_t) consumes.

        ``train_tokens`` > 0 caps the step at roughly that many train
        tokens by slicing whole batch rows (compile-time static, so
        each cap compiles once): the token-budget scheduler's lever for
        shrinking a microbatch into the tick's leftover SLO slack
        instead of skipping training outright.  0 = full batch.
        """
        if train_tokens > 0:
            ref = batch.get("tokens", batch.get("embeds"))
            b, s = int(ref.shape[0]), int(ref.shape[1])
            rows = max(1, min(b, train_tokens // max(s, 1)))
            if rows < b:
                batch = jax.tree.map(lambda x: x[:rows], batch)
                if grad_accum > 1 and rows % grad_accum:
                    grad_accum = 1
        def loss_fn(lora_, microbatch):
            loss, metrics = self.model.forward_loss(
                params, lora_, microbatch, ce_chunk=ce_chunk,
                skip_masked_blocks=skip_masked_blocks)
            return loss, metrics

        with jax.named_scope("train_fwd_bwd"):
            if grad_accum <= 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(lora, batch)
                micro_sqnorm = global_norm(grads) ** 2
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                        + x.shape[1:]), batch)

                def body(carry, mb):
                    g_acc, l_acc, sq_acc = carry
                    (loss, _), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(lora, mb)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32) / grad_accum,
                        g_acc, grads)
                    sq = global_norm(grads) ** 2
                    return (g_acc, l_acc + loss / grad_accum,
                            sq_acc + sq / grad_accum), None

                zeros = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), lora)
                (grads, loss, micro_sqnorm), _ = jax.lax.scan(
                    body, (zeros, jnp.float32(0.0), jnp.float32(0.0)), micro)
                metrics = {"ce_loss": loss}

        with jax.named_scope("optimizer"):
            new_lora, new_opt, opt_metrics = self.optimizer.update(
                grads, opt_state, lora)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        # per-microbatch grad sqnorm feeds the noise-scale estimator
        metrics["micro_grad_sqnorm"] = micro_sqnorm
        metrics["grad_sqnorm"] = jnp.square(metrics["grad_norm"])
        return new_lora, new_opt, metrics

    # ------------------------------------------------------------ serving --
    def prefill_step(self, params: Any, lora: Any,
                     batch: Any) -> Tuple[jax.Array, Any]:
        return self.model.prefill(params, lora, batch)

    def decode_step(self, params: Any, lora: Any, caches: Any,
                    token: jax.Array,
                    pos: jax.Array) -> Tuple[jax.Array, Any]:
        return self.model.decode_step(params, lora, caches, token, pos)

    def encoder_serve_step(self, params: Any, lora: Any,
                           batch: Any) -> jax.Array:
        """Encoder-only 'serving': full-sequence frame classification."""
        hidden, _, _ = self.model.hidden_states(params, lora, batch)
        return hidden @ params["lm_head"]

    # ------------------------------------------------- the paper's fusion --
    def combined_step(self, params: Any, lora: Any, opt_state: AdamWState,
                      train_batch: Any, caches: Any, token: jax.Array,
                      pos: jax.Array, *,
                      serve_lora: Any = None,
                      attn_backend: Optional[str] = None,
                      grad_accum: int = 1,
                      train_tokens: int = 0,
                      serve_adapter_idx: Any = None
                      ) -> Tuple[Any, AdamWState, jax.Array, Any,
                                 Dict[str, jax.Array]]:
        """One fused program: LoRA train step + decode batch, sharing the
        HBM-resident base weights.  XLA schedules both DAGs; the returned
        logits come from the *pre-update* adapter (within-step snapshot
        isolation — matching the paper's subprocess snapshot semantics).

        ``serve_lora`` splits the adapters: decode reads it (the
        *published* snapshot) while the optimizer trains ``lora`` (the
        *shadow* tree) — shadow-adapter double buffering, so a whole
        round of training never perturbs in-flight generation.  Omitted,
        decode uses the training adapter (the pre-PR-5 behaviour).
        ``serve_adapter_idx`` [B] int32 makes ``serve_lora`` a STACKED
        multi-tenant tree (leaves [L, A, din, r]) with per-row slot
        selection — the AdapterRegistry decode wave.
        """
        with jax.named_scope("serve"):
            logits, new_caches = self.model.decode_step(
                params, lora if serve_lora is None else serve_lora,
                caches, token, pos, attn_backend=attn_backend,
                adapter_idx=serve_adapter_idx)
        new_lora, new_opt, metrics = self.train_step(
            params, lora, opt_state, train_batch, grad_accum=grad_accum,
            train_tokens=train_tokens)
        return new_lora, new_opt, logits, new_caches, metrics

    def combined_step_paged(self, params: Any, lora: Any,
                            opt_state: AdamWState, train_batch: Any,
                            caches: Any, token: jax.Array,
                            pos: jax.Array, block_tables: jax.Array,
                            *, ring_len: int = 0,
                            serve_lora: Any = None,
                            attn_backend: Optional[str] = None,
                            grad_accum: int = 1,
                            train_tokens: int = 0,
                            serve_adapter_idx: Any = None
                            ) -> Tuple[Any, AdamWState, jax.Array, Any,
                                       Dict[str, jax.Array]]:
        """``combined_step`` over the paged KV pool: LoRA train step +
        block-table decode tick fused into one program (same pre-update
        snapshot semantics, ``serve_lora`` shadow split, and
        ``serve_adapter_idx`` multi-tenant row selection)."""
        with jax.named_scope("serve"):
            logits, new_caches = self.model.decode_step_paged(
                params, lora if serve_lora is None else serve_lora,
                caches, token, pos, block_tables,
                ring_len=ring_len, attn_backend=attn_backend,
                adapter_idx=serve_adapter_idx)
        new_lora, new_opt, metrics = self.train_step(
            params, lora, opt_state, train_batch, grad_accum=grad_accum,
            train_tokens=train_tokens)
        return new_lora, new_opt, logits, new_caches, metrics

    def combined_prefill_step(self, params: Any, lora: Any,
                              opt_state: AdamWState, train_batch: Any,
                              infer_batch: Any
                              ) -> Tuple[Any, AdamWState, jax.Array,
                                         Any, Dict[str, jax.Array]]:
        """Fused train + prefill variant (used when the co-located
        inference work is prompt processing rather than decode)."""
        logits, caches = self.model.prefill(params, lora, infer_batch)
        new_lora, new_opt, metrics = self.train_step(
            params, lora, opt_state, train_batch)
        return new_lora, new_opt, logits, caches, metrics


def make_engine(cfg: ModelConfig, lr: float = 1e-4,
                weight_decay: float = 0.0) -> Engine:
    return Engine(model=build(cfg),
                  optimizer=AdamW(lr=lr, weight_decay=weight_decay))
