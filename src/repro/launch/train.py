"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 100 --batch 8 --seq 128 --data /tmp/repro_data \
        --ckpt /tmp/repro_ckpt

Wires every subsystem together: synthetic shard generation (once),
foreactor-speculated batch loading, jitted train step on the host mesh,
async foreactor-backed checkpointing with restore-on-start, straggler
accounting.  ``--kill-at N`` aborts at step N to exercise the
crash/restore path (rerun the same command to resume).

:func:`run` is the same driver as a function: it takes the command line
as a list plus config overrides and returns the fit summary.
"""

from __future__ import annotations

import argparse
from dataclasses import replace
from typing import Any, Dict, Optional, Sequence

from repro.checkpoint import CheckpointManager, CheckpointPolicy
from repro.configs import get_config
from repro.core import Foreactor, OSDevice
from repro.data import (DataConfig, ShardedTokenDataset, TokenBatchLoader,
                        write_synthetic_dataset)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


class SimulatedNodeFailure(RuntimeError):
    """What ``--kill-at`` raises at its step."""


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--data", default="/tmp/repro_data")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--records-per-shard", type=int, default=256)
    ap.add_argument("--no-restore", action="store_true")
    ap.add_argument("--serial-ckpt", action="store_true",
                    help="disable write-behind checkpointing (save blocks "
                         "the training thread; the serial baseline)")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="simulate a node failure at this step")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="retention: newest N step-checkpoints to keep")
    ap.add_argument("--keep-spaced", type=int, default=0,
                    help="retention: newest M time-spaced anchor checkpoints")
    ap.add_argument("--spacing-s", type=float, default=3600.0,
                    help="retention: minimum seconds between anchors")
    ap.add_argument("--delta-every", type=int, default=0,
                    help="write K delta checkpoints between full saves "
                         "(0 = every save full)")
    return ap.parse_args(argv)


def run(argv: Optional[Sequence[str]] = None, mesh=None,
        **cfg_override: Any) -> Dict[str, Any]:
    """Train as the command line ``argv`` says and return the fit summary.

    ``cfg_override`` replaces fields of the architecture's config (a depth
    cut is ``n_layers=4``); ``mesh`` defaults to every device of the host.
    A run that ``--kill-at`` stops returns, after its emergency save, the
    summary so far with ``killed`` set; any other failure raises.
    """
    args = parse_args(argv)
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg_override:
        cfg = replace(cfg, **cfg_override)
    if cfg.enc_dec is not None or cfg.visual_stub:
        raise SystemExit("train driver covers LM archs; see examples/ for "
                         "multimodal smoke steps")
    model = build_model(cfg)
    device = OSDevice()
    fa = Foreactor(device=device, backend="io_uring", depth=32)

    dcfg = DataConfig(seq_len=args.seq, batch_size=args.batch, seed=0)
    shard0 = f"{args.data}/shard_00000.rio"
    try:
        device.fstatat(shard0)
    except FileNotFoundError:
        print(f"[train] generating synthetic dataset under {args.data}")
        write_synthetic_dataset(device, args.data, dcfg, args.shards,
                                args.records_per_shard, cfg.vocab_size)
    ds = ShardedTokenDataset(
        device, [f"{args.data}/shard_{i:05d}.rio" for i in range(args.shards)])
    loader = TokenBatchLoader(ds, dcfg, fa=fa)

    ckpt = CheckpointManager(device, args.ckpt, fa=fa, num_shards=4)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                      total_steps=args.steps)
    tcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         log_every=10, restore=not args.no_restore,
                         write_behind=not args.serial_ckpt,
                         retention=CheckpointPolicy(
                             keep_last=args.keep_last,
                             keep_spaced=args.keep_spaced,
                             spacing_s=args.spacing_s),
                         delta_every=args.delta_every)
    trainer = Trainer(model, opt, loader, ckpt,
                      make_host_mesh() if mesh is None else mesh, tcfg)

    if args.kill_at:
        orig = loader.load

        def killing_load(e, s):
            if e * loader.steps_per_epoch + s >= args.kill_at:
                raise SimulatedNodeFailure(
                    f"simulated node failure at step {args.kill_at}")
            return orig(e, s)

        loader.load = killing_load

    try:
        try:
            out = trainer.fit()
        except SimulatedNodeFailure as e:
            out = dict(trainer.summary(), killed=str(e))
    finally:
        loader.close()
        fa.shutdown()
    if out.get("killed"):
        print(f"[train] {out['killed']}: emergency checkpoint at step "
              f"{out['emergency_step']}")
        return out
    mode = "serial" if args.serial_ckpt else "write-behind"
    last = f"{out['losses'][-1]:.4f}" if out["losses"] else "-"
    print(f"[train] done: step {out['final_step']}  "
          f"final loss {last}  "
          f"mean step {1e3 * (out['mean_step_s'] or 0):.0f}ms  "
          f"stragglers {out['stragglers']}  "
          f"ckpt[{mode}] {out['ckpt_saves']} saves, "
          f"{out['ckpt_wait_s']:.2f}s stalled")
    return out


def main() -> None:
    enable_compile_cache()
    if run().get("killed"):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
