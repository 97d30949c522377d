"""Unified model front-door: ``build_model(cfg)`` returns a Model facade
with init / loss / prefill / decode_step bound to the right family."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from . import lm, whisper
from .config import ModelConfig


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    #: (params, batch) -> (loss, counts): the loss with the step's counters
    #: (``moe_assigned``, ``moe_kept`` where the model routes experts)
    loss_and_stats: Callable
    logits: Optional[Callable] = None

    @property
    def is_enc_dec(self) -> bool:
        return self.cfg.enc_dec is not None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.enc_dec is not None:
        return Model(
            cfg=cfg,
            init=lambda rng: whisper.init(cfg, rng),
            loss=lambda params, batch: whisper.loss(cfg, params, batch),
            prefill=lambda params, batch, max_len: whisper.prefill(cfg, params, batch, max_len),
            decode_step=lambda params, cache, token, pos: whisper.decode_step(cfg, params, cache, token, pos),
            loss_and_stats=lambda params, batch: (whisper.loss(cfg, params, batch), {}),
        )
    return Model(
        cfg=cfg,
        init=lambda rng: lm.init(cfg, rng),
        loss=lambda params, batch: lm.loss(cfg, params, batch),
        prefill=lambda params, batch, max_len: lm.prefill(cfg, params, batch, max_len),
        decode_step=lambda params, cache, token, pos: lm.decode_step(cfg, params, cache, token, pos),
        loss_and_stats=lambda params, batch: lm.loss_and_stats(cfg, params, batch),
        logits=lambda params, batch: lm.logits_fn(cfg, params, batch),
    )
