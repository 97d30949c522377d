"""Assigned-architecture registry: ``--arch <id>`` resolution.

Each module defines ``full()`` (the published configuration) and
``smoke()`` (a reduced same-family config that runs a real forward/train
step on CPU).  ``SHAPES`` is the LM shape set the sharding rules must
divide at the published widths.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict

from repro.models.config import ModelConfig

ARCH_IDS = [
    "qwen2_vl_7b",
    "deepseek_v2_236b",
    "granite_moe_3b_a800m",
    "tinyllama_1_1b",
    "gemma_2b",
    "command_r_35b",
    "gemma_7b",
    "whisper_tiny",
    "zamba2_1_2b",
    "rwkv6_7b",
    "deepseek_v2_lite",
]

# public ids as given in the assignment -> module names
ALIASES = {
    "qwen2-vl-7b": "qwen2_vl_7b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "gemma-2b": "gemma_2b",
    "command-r-35b": "command_r_35b",
    "gemma-7b": "gemma_7b",
    "whisper-tiny": "whisper_tiny",
    "zamba2-1.2b": "zamba2_1_2b",
    "rwkv6-7b": "rwkv6_7b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def resolve(arch: str) -> str:
    return ALIASES.get(arch, arch)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{resolve(arch)}")
    return mod.smoke() if smoke else mod.full()
