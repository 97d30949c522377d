"""Train-step and generation builders (pjit-able pure functions)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.api import Model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update


def make_train_state(model: Model, opt_cfg: AdamWConfig, rng) -> Dict[str, Any]:
    params = model.init(rng)
    return {"params": params, "opt": adamw_init(opt_cfg, params)}


def make_train_step(model: Model, opt_cfg: AdamWConfig):
    def train_step(state: Dict[str, Any], batch: Dict[str, jax.Array]):
        (loss, counts), grads = jax.value_and_grad(
            model.loss_and_stats, has_aux=True)(state["params"], batch)
        new_params, new_opt, metrics = adamw_update(
            opt_cfg, state["params"], grads, state["opt"])
        metrics = dict(metrics, loss=loss, **counts)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_generate_loop(model: Model, steps: int):
    """Greedy generation: prefill + `steps` decode steps under one jit."""

    def generate(params, batch, max_len):
        logits, cache = model.prefill(params, batch, max_len)
        B, S = batch["tokens"].shape
        tok = jnp.argmax(logits[:, : model.cfg.vocab_size], -1)

        def body(carry, t):
            tok, cache = carry
            pos = jnp.full((B,), S + t, jnp.int32)
            logits, cache = model.decode_step(params, cache, tok, pos)
            tok = jnp.argmax(logits[:, : model.cfg.vocab_size], -1)
            return (tok, cache), tok

        (_, _), toks = jax.lax.scan(body, (tok, cache), jnp.arange(steps))
        return jnp.moveaxis(toks, 0, 1)  # (B, steps)

    return generate
