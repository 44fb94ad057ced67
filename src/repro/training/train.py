"""Training step builder: loss -> grads (with microbatched accumulation) ->
AdamW -> metrics.  Distribution comes from in/out shardings (GSPMD inserts
the hierarchical reduce-scatter/all-reduce across (pod, data)); optional
explicit int8-compressed gradient all-reduce is available through
``repro.distributed.compress`` (shard_map path).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..models.config import ArchConfig
from . import optimizer as opt


def make_loss_fn(cfg: ArchConfig):
    def loss_fn(params, batch):
        return T.loss_fn(params, cfg, batch)
    return loss_fn


def make_train_step(cfg: ArchConfig, ocfg: opt.AdamWConfig,
                    grad_accum: int = 1, fused_backward: bool = False):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  With grad_accum > 1 the global batch is split along axis 0
    into microbatches accumulated under a lax.scan (keeps peak activation
    memory at one microbatch).

    ``fused_backward=True`` routes the model's mHC stream mixers through
    their custom-VJP variant at trace time: the backward pass's stream
    cotangents run the EXTRACTED ``mhc_stream_bwd`` fusion chain
    (DESIGN.md §16) instead of XLA einsums.  No-op for configs without
    hyper-connections."""
    from ..models import layers as L
    loss_fn = make_loss_fn(cfg)

    def grads_of(params, batch):
        if fused_backward:
            # trace-time dispatch: the scope only matters while the
            # jaxpr is built, so it composes with jit/scan
            with L.mhc_post_impl("fused_bwd"):
                return jax.value_and_grad(loss_fn)(params, batch)
        return jax.value_and_grad(loss_fn)(params, batch)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            loss, grads = grads_of(params, batch)
        else:
            def micro(b):
                return jax.tree.map(
                    lambda x: x.reshape(grad_accum,
                                        x.shape[0] // grad_accum,
                                        *x.shape[1:]), b)
            mb = micro(batch)

            def body(carry, b):
                acc, lsum = carry
                l, g = grads_of(params, b)
                acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return (acc, lsum + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(body, (zeros, 0.0), mb)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
        new_params, new_state, metrics = opt.apply(ocfg, params, opt_state,
                                                   grads)
        metrics["loss"] = loss
        return new_params, new_state, metrics

    return train_step


def make_sharded_train_step(cfg: ArchConfig, ocfg: opt.AdamWConfig, mesh,
                            batch_specs: Dict[str, Any],
                            grad_accum: int = 1,
                            fused_backward: bool = False):
    """jit the train step with explicit in/out shardings for `mesh`:
    params and state come out as they went in, so the next step takes them
    as they are, and both are donated.  Returns (step, (param shardings,
    state shardings, batch shardings))."""
    from ..distributed import sharding as S
    if mesh.size > 1 and cfg.attn_impl == "auto":
        # GSPMD cannot partition a Mosaic kernel: until the flash chain is
        # wrapped in shard_map, a sharded step runs XLA attention
        cfg = cfg.scaled(attn_impl="xla")
    step = make_train_step(cfg, ocfg, grad_accum,
                           fused_backward=fused_backward)
    aparams = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    pshard = S.param_shardings(mesh, aparams)
    astate = jax.eval_shape(opt.init, aparams)
    oshard = S.opt_state_shardings(mesh, astate, aparams)
    bshard = S.batch_shardings(mesh, batch_specs)
    metrics_shard = {"grad_norm": jax.NamedSharding(mesh, jax.P()),
                     "lr": jax.NamedSharding(mesh, jax.P()),
                     "loss": jax.NamedSharding(mesh, jax.P())}
    jitted = jax.jit(
        step,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, metrics_shard),
        donate_argnums=(0, 1),
    )
    return jitted, (pshard, oshard, bshard)


def init_sharded(cfg: ArchConfig, pshard, oshard, seed: int = 0):
    """Params (from ``seed``) and AdamW state, created directly in their
    shardings: at published widths the optimizer state does not fit one
    chip."""
    params = jax.jit(lambda: T.init_params(jax.random.PRNGKey(seed), cfg),
                     out_shardings=pshard)()
    return params, jax.jit(opt.init, out_shardings=oshard)(params)
