#!/usr/bin/env python3
"""Bring-up smoke of the training path on a TPU: train, write-behind save,
kill, resume — through ``repro.launch.train.run``, in one process.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --chips 4     # four-chip host: sharded train/save/
                                       # restore against one chip
    python chip_smoke.py --rehearse    # CPU rehearsal: tiny bf16 config,
                                       # Pallas in interpret mode

One chip runs TinyLlama-1.1B at its published widths, cut to 4 layers,
at batch 8 x seq 2048:

1. train with write-behind saves and die at ``--kill-at`` (emergency save);
2. resume in a fresh trainer over the same directories: it must restore
   the emergency step, byte-identical (per-leaf CRCs against the
   manifest), then take more steps with finite losses;
3. run one loss with the Pallas attention kernel and with the XLA
   reference and compare them.

``--chips 4`` runs only the four-chip path: a few steps on the host mesh,
a save and a restore there, and the same steps on one chip of the same
process, whose per-step losses must agree.

Timings, bytes and cache counts printed on the way are one-off readings,
not benchmark metrics.  Every failed check, every exception and a missing
accelerator exit non-zero; the last line of standard output is
``{"ok": true, "device": {...}}`` only when everything passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
import zlib
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import OSDevice  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402

ARCH = "tinyllama-1.1b"

#: Pallas vs reference, relative: one bf16 ulp (2^-8).  Both paths take
#: the same bf16 inputs; they round attention probabilities and outputs to
#: bf16 at different points, so single elements may differ by about an
#: ulp, and a loss or output cannot be asked to agree more closely.  A
#: wrong mask or block index moves them far more.
BF16_RTOL = 2.0 ** -8
#: attention outputs are convex combinations of N(0, 1) values: each may
#: differ by a few bf16 ulps of the larger of |o| and 1 (an ulp is at
#: most 2^-7 of the value), so four of them: 2^-5 relative to that
ATTN_RTOL = 2.0 ** -5


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


@dataclass(frozen=True)
class Size:
    smoke_cfg: bool          # the registry's reduced config instead of full()
    cfg_override: Dict[str, Any] = field(default_factory=dict)
    batch: int = 8
    seq: int = 2048
    records_per_shard: int = 32
    kernel_impl: str = "pallas"   # what the kernel check compares with "ref"


ONE_CHIP = Size(smoke_cfg=False, cfg_override={"n_layers": 4})
#: tiny, but in the chip's dtypes, with the Pallas kernel interpreted on
#: the training path too (batch 4 divides over four virtual devices)
REHEARSAL = Size(smoke_cfg=True,
                 cfg_override={"param_dtype": "bfloat16",
                               "compute_dtype": "bfloat16",
                               "attn_impl": "interpret"},
                 batch=4, seq=32, records_per_shard=16,
                 kernel_impl="interpret")

# the schedule of the one-chip phases: periodic write-behind saves every
# CKPT_EVERY steps, a node failure at KILL_AT, a resume to STEPS
STEPS, CKPT_EVERY, KILL_AT = 8, 4, 6


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> Dict[str, Any]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events, while the ``with`` block runs."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def __enter__(self) -> "CompileStats":
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def report(self) -> str:
        return (f"compile {self.seconds:.2f}s; persistent cache hits "
                f"{self.hits}, misses {self.misses}")


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def check_matches_manifest(state: Any, ckpt_dir: str, step: int) -> None:
    """Per-leaf name, dtype, shape and CRC32 of ``state`` against the
    manifest the save of ``step`` wrote."""
    mgr = CheckpointManager(OSDevice(), ckpt_dir, num_shards=4)
    try:
        leaves = mgr.read_manifest(step)["leaves"]
    finally:
        mgr.fa.shutdown()
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    check(len(flat) == len(leaves),
          f"restored state has {len(flat)} leaves, manifest {len(leaves)}")
    for (path, leaf), m in zip(flat, leaves):
        name = jax.tree_util.keystr(path)
        host = np.asarray(leaf)
        check(name == m["name"], f"leaf {name} != manifest {m['name']}")
        check(str(host.dtype) == m["dtype"] and list(host.shape) == m["shape"],
              f"{name}: {host.dtype}{list(host.shape)} != "
              f"{m['dtype']}{m['shape']}")
        check(zlib.crc32(np.ascontiguousarray(host)) == m["crc32"],
              f"{name}: crc32 differs")
    log(f"restored state matches the step-{step} manifest: "
        f"{len(leaves)} leaves, crc32 each")


def check_losses(losses: List[float], n: int, what: str) -> None:
    check(len(losses) == n, f"{what}: {len(losses)} losses, expected {n}")
    check(all(math.isfinite(x) for x in losses), f"{what}: loss not finite")


def placement(state: Any) -> Dict[Any, int]:
    """Print where each parameter leaf lives; return bytes per device over
    the whole train state."""
    per_dev: Dict[Any, int] = {}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    for path, leaf in jax.tree_util.tree_flatten_with_path(state["params"])[0]:
        log(f"  params{jax.tree_util.keystr(path)} {leaf.dtype}"
            f"{list(leaf.shape)}: {leaf.sharding.spec}")
    for d, n in sorted(per_dev.items(), key=lambda kv: kv[0].id):
        log(f"  device {d.id}: {n / 1e9:.3f} GB of train state")
    return per_dev


def memory_line() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    now = stats.get("bytes_in_use")
    dev = ("device 0 peak_bytes_in_use not reported" if peak is None else
           f"device 0 peak_bytes_in_use {peak / 1e9:.3f} GB, "
           f"bytes_in_use {now / 1e9:.3f} GB")
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    return (f"{dev}; host RSS {rss / 1e9:.3f} GB, "
            f"peak {peak_rss / 1e9:.3f} GB")


def train_argv(size: Size, work: str, ckpt: str, steps: int,
               ckpt_every: int, kill_at: int = 0) -> List[str]:
    argv = ["--arch", ARCH, "--batch", str(size.batch), "--seq", str(size.seq),
            "--data", f"{work}/data", "--ckpt", ckpt, "--shards", "4",
            "--records-per-shard", str(size.records_per_shard),
            "--keep-last", "1", "--steps", str(steps),
            "--ckpt-every", str(ckpt_every)]
    if kill_at:
        argv += ["--kill-at", str(kill_at)]
    if size.smoke_cfg:
        argv.append("--smoke")
    return argv


# -- one chip -----------------------------------------------------------------
def train_kill_resume(size: Size, work: str) -> Dict[str, Any]:
    ckpt = f"{work}/ckpt"

    log(f"train: {STEPS} steps, write-behind save every {CKPT_EVERY}, "
        f"node failure at step {KILL_AT}")
    t0 = time.perf_counter()
    out = train.run(train_argv(size, work, ckpt, STEPS, CKPT_EVERY, KILL_AT),
                    **size.cfg_override)
    to_first = out["first_step_at"] - t0
    check(bool(out.get("killed")), "the run was not killed at --kill-at")
    check(out["emergency_step"] == KILL_AT,
          f"emergency save at {out['emergency_step']}, expected {KILL_AT}")
    check(out["ckpt_saves"] == 1, f"{out['ckpt_saves']} write-behind saves, "
          "expected 1")
    check_losses(out["losses"], KILL_AT, "train")
    mgr = CheckpointManager(OSDevice(), ckpt, num_shards=4)
    try:
        latest = mgr.latest_step()
        extra = mgr.read_manifest(latest)["extra"] if latest is not None else {}
    finally:
        mgr.fa.shutdown()
    check(latest == KILL_AT and extra.get("emergency") is True,
          f"newest committed step {latest} ({extra}) is not the emergency save")
    log(f"after the killed run: {memory_line()}")

    log("resume: a fresh trainer over the same directories, no step run")
    back = train.run(train_argv(size, work, ckpt, KILL_AT, CKPT_EVERY),
                     **size.cfg_override)
    check(back["restored_step"] == KILL_AT,
          f"resumed from {back['restored_step']}, expected the emergency "
          f"step {KILL_AT} (None is a fresh start)")
    state_bytes = tree_bytes(back["state"])
    check_matches_manifest(back["state"], ckpt, KILL_AT)
    log(f"resident with the restored state: {memory_line()}")
    del back

    log(f"resume again and train to step {STEPS}")
    t0 = time.perf_counter()
    cont = train.run(train_argv(size, work, ckpt, STEPS, CKPT_EVERY),
                     **size.cfg_override)
    to_first_resumed = cont["first_step_at"] - t0
    check(cont["restored_step"] == KILL_AT,
          f"second resume from {cont['restored_step']}, expected {KILL_AT}")
    check(cont["final_step"] == STEPS, f"stopped at {cont['final_step']}")
    check_losses(cont["losses"], STEPS - KILL_AT, "resumed training")

    mb = state_bytes / 1e6
    log(f"train state {state_bytes / 1e9:.3f} GB")
    log(f"step time (to block_until_ready), mean of steps 1..{KILL_AT - 1}: "
        f"{1e3 * out['mean_step_s']:.1f} ms; start to first step "
        f"(compile included) {to_first:.2f}s")
    log(f"stall per write-behind save: "
        f"{out['ckpt_wait_s'] / out['ckpt_saves']:.3f}s")
    log(f"emergency save: {out['emergency_save_s']:.2f}s, "
        f"{mb / out['emergency_save_s']:.0f} MB/s")
    log(f"restore: {cont['restore_s']:.2f}s, {mb / cont['restore_s']:.0f} MB/s")
    log(f"start to first resumed step: {to_first_resumed:.2f}s")
    log(f"losses: train {out['losses']}, resumed {cont['losses']}")
    return cont["state"]["params"]


def kernel_check(size: Size, params: Any, on_chip: bool) -> None:
    """One loss at the smoke's shapes with the Pallas kernel and with the
    XLA reference; plus the attention op alone at the same shapes."""
    cfg = get_config(ARCH, smoke=size.smoke_cfg)
    cfg = replace(cfg, **size.cfg_override)
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (size.batch, size.seq + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    losses = {}
    for impl in (size.kernel_impl, "ref"):
        model = build_model(replace(cfg, attn_impl=impl))
        fn = jax.jit(model.loss).lower(params, batch).compile()
        if on_chip and impl != "ref":
            check("tpu_custom_call" in fn.as_text(), "no Pallas kernel in the loss")
        losses[impl] = float(fn(params, batch))
    a, b = losses[size.kernel_impl], losses["ref"]
    log(f"kernel check: loss {size.kernel_impl} {a:.6f} vs ref {b:.6f} "
        f"(|diff| {abs(a - b):.2e}, limit {BF16_RTOL * abs(b):.2e})")
    check(math.isfinite(a) and abs(a - b) <= BF16_RTOL * abs(b),
          "Pallas and reference losses disagree")

    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kq, kk, kv = jax.random.split(key, 3)
    dt = cfg.compute_jdtype()
    q = jax.random.normal(kq, (size.batch, H, size.seq, D), dt)
    k = jax.random.normal(kk, (size.batch, KV, size.seq, D), dt)
    v = jax.random.normal(kv, (size.batch, KV, size.seq, D), dt)
    outs = [jax.jit(lambda q, k, v, i=impl: ops.attention(q, k, v, impl=i))(q, k, v)
            for impl in (size.kernel_impl, "ref")]
    a, b = (o.astype(jnp.float32) for o in outs)
    err = float(jnp.max(jnp.abs(a - b) / jnp.maximum(jnp.abs(b), 1.0)))
    log(f"kernel check: attention {list(q.shape)} max |diff| / max(|ref|, 1) "
        f"{err:.2e} (limit {ATTN_RTOL:.2e})")
    check(err <= ATTN_RTOL, "Pallas and reference attention disagree")


# -- four chips ---------------------------------------------------------------
def four_chips(size: Size, work: str, n: int = 4, steps: int = 3) -> None:
    check(len(jax.devices()) >= n, f"{len(jax.devices())} devices, need {n}")
    mesh = make_host_mesh(jax.devices()[:n])
    one = make_host_mesh(jax.devices()[:1])
    ckpt = f"{work}/ckpt{n}"

    log(f"{n} chips: {steps} steps on the host mesh {dict(mesh.shape)}, "
        "then save")
    out = train.run(train_argv(size, work, ckpt, steps, 0), mesh=mesh,
                    **size.cfg_override)
    check_losses(out["losses"], steps, f"{n}-chip training")
    per_dev = placement(out["state"])
    total = tree_bytes(out["state"])
    check(len(per_dev) == n and all(b > 0 for b in per_dev.values()),
          f"train state on {len(per_dev)} of {n} devices")
    check(max(per_dev.values()) < total,
          "one device holds the whole train state")
    del out["state"]

    log(f"{n} chips: restore onto the mesh")
    back = train.run(train_argv(size, work, ckpt, steps, 0), mesh=mesh,
                     **size.cfg_override)
    check(back["restored_step"] == steps,
          f"restored {back['restored_step']}, expected {steps}")
    check_matches_manifest(back["state"], ckpt, steps)
    check(placement(back["state"]) == per_dev,
          "the restored state is placed unlike the saved one")
    del back

    log("one chip of the same process: the same steps")
    ref = train.run(train_argv(size, work, f"{work}/ckpt1", steps, 0),
                    mesh=one, **size.cfg_override)
    for i, (a, b) in enumerate(zip(out["losses"], ref["losses"])):
        log(f"  step {i}: loss {n} chips {a:.6f}, 1 chip {b:.6f}")
        # partitioning reorders the bf16 products' f32 sums (and the
        # gradient reduction), so the steps agree to bf16 rounding
        check(abs(a - b) <= BF16_RTOL * abs(b),
              f"step {i}: {n}-chip loss {a} vs one chip {b}")
    log(f"{n}-chip step {1e3 * out['mean_step_s']:.1f} ms, one chip "
        f"{1e3 * ref['mean_step_s']:.1f} ms (one-off readings)")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and its one-chip "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny bf16 config, interpreted Pallas")
    args = ap.parse_args(argv)

    info = device_info()
    log(f"device: {info['platform']} {info['kind']!r} x{info['count']}")
    if args.rehearse:
        check(info["platform"] == "cpu", "--rehearse runs on the CPU only")
    else:
        check(info["platform"] == "tpu", f"no TPU: JAX found {info['platform']}")
    size = REHEARSAL if args.rehearse else ONE_CHIP
    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    if size.smoke_cfg:
        log(f"config: {get_config(ARCH, smoke=True).name} with "
            f"{size.cfg_override}")
    else:
        log(f"reduced: n_layers {get_config(ARCH).n_layers}"
            f"→{size.cfg_override['n_layers']}")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        log(f"working files under {work}: "
            f"{shutil.disk_usage(work).free / 1e9:.1f} GB free")
        with CompileStats() as stats:
            if args.chips == 4:
                four_chips(size, work)
            else:
                params = train_kill_resume(size, work)
                kernel_check(size, params, on_chip=not args.rehearse)
        log(stats.report())
        log(memory_line())
    finally:
        shutil.rmtree(work, ignore_errors=True)
    result: Dict[str, Any] = {"ok": True, "device": info}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
