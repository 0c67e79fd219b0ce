"""Model facade: config -> init / train_step / prefill_step / decode_step.

This is the public API the launcher, dry-run, examples and tests use.
Everything is expressed as pure functions over pytrees so the runtime
can jit them with explicit shardings (and re-jit after elastic resize).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..optim.adamw import (OptConfig, OptState, apply_updates,
                           init_opt_state, opt_state_specs)
from ..parallel.sharding import ShardingCtx
from .config import ArchConfig, ShapeConfig
from .layers import materialize_tree, tree_shapes, tree_shardings
from .transformer import (cache_shardings, decode_step, forward,
                          init_cache_specs, init_specs, loss_fn)


@dataclass
class Model:
    cfg: ArchConfig
    ctx: ShardingCtx
    opt: OptConfig

    # -------------------------------------------------------------- #
    # params / state
    # -------------------------------------------------------------- #
    def param_specs(self):
        return init_specs(self.cfg)

    def init_params(self, key: jax.Array, dtype: Optional[str] = None):
        """Random parameters; ``dtype`` overrides the specs' float32
        (serving draws its weights directly in ``cfg.dtype``)."""
        return materialize_tree(self.param_specs(), key, dtype)

    def param_shardings(self):
        return tree_shardings(self.param_specs(), self.ctx)

    def param_shapes(self):
        return tree_shapes(self.param_specs())

    def init_opt(self, params):
        return init_opt_state(params, self.opt)

    def opt_shardings(self):
        specs = opt_state_specs(self.param_specs(), self.opt)
        return tree_shardings(specs, self.ctx)

    def opt_shapes(self):
        specs = opt_state_specs(self.param_specs(), self.opt)
        return tree_shapes(specs)

    # -------------------------------------------------------------- #
    # steps
    # -------------------------------------------------------------- #
    def _value_and_grad(self, params, batch: Dict):
        if self.cfg.bf16_grads:
            # §Perf mixed precision: differentiate w.r.t. a bf16 compute
            # copy — FSDP weight gathers and gradient reductions move
            # bf16 (half the bytes); the fp32 master updates in fp32.
            cdt = jnp.dtype(self.cfg.dtype)
            params = jax.tree_util.tree_map(
                lambda a: a.astype(cdt)
                if a.dtype == jnp.float32 else a, params)
        return jax.value_and_grad(
            lambda p: loss_fn(p, self.cfg, self.ctx, batch))(params)

    def train_step(self, params, opt_state: OptState, batch: Dict):
        """One optimizer step; returns (params, opt_state, metrics).

        With ``cfg.grad_accum > 1`` the global batch is split into
        microbatches scanned sequentially, accumulating fp32 grads —
        activation memory drops by the factor (this is how the 400B MoE
        trains on a SINGLE pod; see EXPERIMENTS.md §Dry-run)."""
        k = self.cfg.grad_accum
        if k <= 1:
            loss, grads = self._value_and_grad(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:]),
                batch)

            def body(acc, mb):
                l, g = self._value_and_grad(params, mb)
                acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g)
                return acc, l

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(body, zeros, micro)
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            loss = jnp.mean(losses)
        params, opt_state = apply_updates(params, grads, opt_state, self.opt)
        return params, opt_state, {"loss": loss}

    def eval_step(self, params, batch: Dict):
        return loss_fn(params, self.cfg, self.ctx, batch)

    def _refuse_codebooks(self, what: str) -> None:
        if self.cfg.is_codebook:
            raise NotImplementedError(
                f"{self.cfg.name}: {what} is not supported for a codebook "
                f"model (serving it needs a cross-attention cache); it "
                f"trains only")

    def prefill_step(self, params, batch: Dict):
        """Full-context forward returning (last-token logits, cache).
        Only the final position goes through the LM head (§Perf: the
        [b, s, vocab] logits buffer never materializes)."""
        self._refuse_codebooks("prefill_step")
        mode = "last" if self.cfg.prefill_last_logits else "all"
        logits, cache = forward(params, self.cfg, self.ctx,
                                tokens=batch.get("tokens"),
                                embeds=batch.get("embeds"), want_cache=True,
                                logits_positions=mode)
        return logits[:, -1:, :], cache

    def serve_step(self, params, cache, batch: Dict, pos):
        """One decode step: (logits [b,1,v], new cache)."""
        self._refuse_codebooks("serve_step")
        return decode_step(params, cache, self.cfg, self.ctx,
                           tokens=batch.get("tokens"),
                           embeds=batch.get("embeds"), pos=pos)

    # -------------------------------------------------------------- #
    # input / cache specs (ShapeDtypeStructs for AOT lowering)
    # -------------------------------------------------------------- #
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of a cell.

        The vlm modality frontend is a stub: ``input_specs`` provides
        precomputed patch embeddings [b, s, d_model].  A codebook model
        takes codes [b, K, s] in the delay pattern, its conditioning
        states [b, t, cond_dim] with their mask [b, t], and labels
        [b, K, s]."""
        b = shape.global_batch
        s = shape.seq_len if shape.mode != "decode" else 1
        h = jax.ShapeDtypeStruct
        cfg = self.cfg
        if cfg.is_codebook:
            k, t = cfg.n_codebooks, shape.cond_len
            batch = {"codes": h((b, k, s), jnp.int32),
                     "labels": h((b, k, s), jnp.int32)}
            if cfg.cond_dim:
                batch["cond"] = h((b, t, cfg.cond_dim), jnp.float32)
                batch["cond_mask"] = h((b, t), jnp.bool_)
            return batch
        stub = self.cfg.frontend != "token"
        batch: Dict[str, Any] = {}
        if stub:
            batch["embeds"] = h((b, s, self.cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = h((b, s), jnp.int32)
        if shape.mode == "train":
            batch["labels"] = h((b, s), jnp.int32)
        return batch

    def input_shardings(self, shape: ShapeConfig) -> Dict[str, Any]:
        sh = self.ctx.sharding
        seq_ax = "seq" if shape.mode != "decode" else None
        if self.cfg.is_codebook:
            out = {"codes": sh("batch", None, seq_ax),
                   "labels": sh("batch", None, seq_ax)}
            if self.cfg.cond_dim:
                out["cond"] = sh("batch", None, "embed")
                out["cond_mask"] = sh("batch", None)
            return out
        stub = self.cfg.frontend != "token"
        out: Dict[str, Any] = {}
        if stub:
            out["embeds"] = sh("batch", seq_ax, "embed")
        else:
            out["tokens"] = sh("batch", seq_ax)
        if shape.mode == "train":
            out["labels"] = sh("batch", seq_ax)
        return out

    def cache_specs(self, shape: ShapeConfig):
        return init_cache_specs(self.cfg, shape.global_batch, shape.seq_len)

    def cache_shardings(self):
        return cache_shardings(self.cfg, self.ctx)

    def init_cache(self, shape: ShapeConfig):
        return jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), self.cache_specs(shape))


def make_model(cfg: ArchConfig, ctx: Optional[ShardingCtx] = None,
               opt: Optional[OptConfig] = None) -> Model:
    ctx = ctx or ShardingCtx()
    if opt is None:
        opt = OptConfig(kind=cfg.optimizer)
    return Model(cfg=cfg, ctx=ctx, opt=opt)
