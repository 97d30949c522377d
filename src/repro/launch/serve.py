"""Serving driver: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_generate_loop
from repro.models import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.visual_stub:
        batch["visual_embeds"] = jax.random.normal(
            key, (args.batch, 8, cfg.d_model), jnp.float32)
    if cfg.enc_dec is not None:
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_dec.n_audio_ctx, cfg.d_model), jnp.float32)

    gen = make_generate_loop(model, args.gen)
    max_len = args.prompt_len + args.gen + 1
    with jax.set_mesh(make_host_mesh()):
        jitted = jax.jit(gen, static_argnums=(2,))
        t0 = time.perf_counter()
        toks = jitted(params, batch, max_len)
        toks.block_until_ready()
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = jitted(params, batch, max_len)
        toks.block_until_ready()
        t_warm = time.perf_counter() - t0
    tput = args.batch * args.gen / t_warm
    print(f"[serve] generated {toks.shape} tokens; "
          f"first(incl compile)={t_first:.2f}s warm={t_warm*1e3:.0f}ms "
          f"({tput:.0f} tok/s)")
    print("[serve] sample:", toks[0, :12].tolist())


if __name__ == "__main__":
    main()
