"""The timed paths: training through ``Trainer.fit`` and cold resumes.

Both build the objects ``repro.launch.train.run`` wires, with its settings
(``OSDevice``, ``Foreactor(backend="io_uring", depth=32)``,
``TokenBatchLoader``, ``CheckpointManager(num_shards=4)``, the host mesh),
and steer them from outside through per-instance wrappers only:

* ``loader.load`` opens and closes the window (``fit`` has no time limit,
  so the window ends by raising out of ``load`` once the trainer's
  checkpoint manager is detached, which skips the emergency save and the
  final save ``fit`` would otherwise make);
* the trainer's step is wrapped to read the first steps' optimizer state
  for the comparison with the reference;
* the manager's ``save_async``/``save``/``restore_latest`` carry spans.

A ``fault`` (tests only) breaks the timed path underneath so that the
comparison can be seen to fail.
"""

from __future__ import annotations

import gc
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import data as bdata
from .digest import host_digests, tree_digest
from .spans import Spans


class WindowClosed(Exception):
    """Raised out of ``loader.load`` to end ``fit`` at the window's close."""


@dataclass
class RunContext:
    cell: Any                       # spec.Cell, rehearsal overrides applied
    seed: int
    seconds: float
    trace: bool
    work: str                       # scratch directory inside the checkout
    chips: int = 1
    fault: Optional[str] = None
    keep_grad1: bool = False        # keep the reference's first gradient
    spans: Spans = field(default_factory=Spans)
    trace_dir: Optional[str] = None
    trace_data: Any = None          # bench.trace.Trace of a traced run
    # filled by the drivers
    window: Optional[tuple] = None
    out: Dict[str, Any] = field(default_factory=dict)
    checks: Dict[str, tuple] = field(default_factory=dict)   # name -> (value, limit)
    stats: Dict[str, float] = field(default_factory=dict)


# -- wiring -----------------------------------------------------------------------
def model_config(cell):
    from repro.models.config import ModelConfig, MoEConfig

    pc = dict(cell.config["program_config"])
    moe = pc.pop("moe", None)
    return ModelConfig(**pc, moe=MoEConfig(**moe) if moe else None)


def build_model(cell):
    """The system's model with the benchmark's seeded weights as its init."""
    from dataclasses import replace

    from repro.models import build_model as program_model

    from reference import load_reference

    ref = load_reference(cell.config)
    model = program_model(model_config(cell))
    return replace(model, init=lambda rng: ref.init_params(cell.config, rng)), ref


def opt_config(cell):
    from repro.optim.adamw import AdamWConfig

    o = cell.config["assumed"]["optimizer"]
    return AdamWConfig(**o)


def host_mesh(chips: int):
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(jax.devices()[:chips])


def _loader(ctx: RunContext, device, fa, tokens: np.ndarray):
    from repro.data import DataConfig, ShardedTokenDataset, TokenBatchLoader

    t = ctx.cell.traffic
    paths = bdata.write_shards(device, f"{ctx.work}/data", tokens, t["shards"])
    bdata.evict(f"{ctx.work}/data")
    ds = ShardedTokenDataset(device, paths)
    dcfg = DataConfig(seq_len=t["seq_len"], batch_size=t["batch"], seed=ctx.seed)
    return TokenBatchLoader(ds, dcfg, fa=fa)


def _tokens(ctx: RunContext) -> np.ndarray:
    t = ctx.cell.traffic
    return bdata.make_tokens(ctx.seed, t["records"], t["seq_len"] + 1,
                             int(ctx.cell.config["vocab_size"]))


def _flip_one_bit(tree):
    """The tree with one bit of its second-to-last leaf flipped (a fault)."""
    leaves, td = jax.tree_util.tree_flatten(tree)
    bad = np.array(leaves[-2], copy=True)
    bad.reshape(-1).view(np.uint8)[0] ^= 1
    leaves[-2] = bad
    return jax.tree_util.tree_unflatten(td, leaves)


def _norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _state_bytes(like) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(like)))


def _peak_bytes(chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:chips]:
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" in s:
            peaks.append(int(s["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _abstract(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def _step_memory_bytes(fn, args) -> Optional[int]:
    """Device bytes the compiled step holds at once, by XLA's memory
    analysis: its arguments, the outputs not aliased to them, and its
    temporaries (``peak_bytes_in_use`` leaves the temporaries out)."""
    if not hasattr(fn, "lower"):
        return None                  # a fault put a plain function in its place
    ma = fn.lower(*args).compile().memory_analysis()
    if ma is None:
        return None
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _host_peak_rss() -> int:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class _Window:
    """Opens the profiler (traced runs) and the ``window`` span, and counts
    the backend compiles that happen while the window is open (there
    should be none; the count is printed, not compared)."""

    def __init__(self, ctx: RunContext):
        self.ctx = ctx
        self.ann = None
        self.t0 = self.t1 = None
        ctx.out["window_compiles"] = 0

    def _compiled(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration" \
                and self.t0 is not None and self.t1 is None:
            self.ctx.out["window_compiles"] += 1

    def open(self) -> None:
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        if self.ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # keep the host's pace
            jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation("bench:window")
            self.ann.__enter__()
        self.t0 = time.perf_counter()

    def close(self) -> None:
        self.t1 = time.perf_counter()
        jax.monitoring.unregister_event_duration_listener(self._compiled)
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        self.ctx.window = (self.t0, self.t1)

    def stop_trace(self) -> None:
        if self.ctx.trace:
            jax.profiler.stop_trace()


# -- training ---------------------------------------------------------------------
def run_train(ctx: RunContext) -> None:
    from repro.checkpoint import CheckpointManager, CheckpointPolicy
    from repro.core import Foreactor, OSDevice
    from repro.launch.steps import make_train_state, make_train_step
    from repro.runtime import Trainer, TrainerConfig

    t = ctx.cell.traffic
    W = int(t["warmup_steps"])
    if W < 3:
        raise ValueError("warmup_steps must cover the three compared steps")
    save = bool(t.get("save_at_window_start"))
    B, S = int(t["batch"]), int(t["seq_len"])
    model, ref = build_model(ctx.cell)
    opt = opt_config(ctx.cell)
    device = OSDevice()
    fa = Foreactor(device=device, backend="io_uring", depth=32)
    tokens = _tokens(ctx)
    loader = _loader(ctx, device, fa, tokens)
    mgr = CheckpointManager(device, f"{ctx.work}/ckpt", fa=fa, num_shards=4) \
        if save else None
    tcfg = TrainerConfig(steps=10 ** 9, ckpt_every=W + 1 if save else 0,
                         log_every=0, seed=ctx.seed, restore=False,
                         write_behind=True,
                         retention=CheckpointPolicy(keep_last=1) if save else None)
    trainer = Trainer(model, opt, loader, mgr, host_mesh(ctx.chips), tcfg)
    key = jax.random.PRNGKey(ctx.seed)
    ctx.out["train_state_bytes"] = _state_bytes(jax.eval_shape(
        lambda r: make_train_state(model, opt, r), key))
    spe = loader.steps_per_epoch
    win = _Window(ctx)
    cap: Dict[str, Any] = {}
    batches: Dict[int, Dict[str, np.ndarray]] = {}

    # -- loader: the window's clock, input spans, the data check's record
    orig_load = loader.load

    def load(e, s):
        g = e * spe + s
        if g == W:
            win.open()
        elif g > W and time.perf_counter() - win.t0 >= ctx.seconds:
            win.close()
            cap["window_steps"] = g - W
            trainer.ckpt = None
            raise WindowClosed()
        if g == W + 1 and save:
            tcfg.ckpt_every = 0          # one save, issued at the window's first step
        with ctx.spans.span("input"):
            batch = orig_load(e, s)
        if ctx.fault == "token_altered" and g == W:
            tok = batch["tokens"].copy()
            tok[0, 0] = (tok[0, 0] + 1) % int(ctx.cell.config["vocab_size"])
            batch = dict(batch, tokens=tok)
        batches[g] = batch
        return batch

    loader.load = load

    orig_place = trainer._place_batch

    def place(batch):
        with ctx.spans.span("put"):
            return orig_place(batch)

    trainer._place_batch = place

    # -- step: reads of the first steps' state
    orig_jit = trainer._jit_step
    norms = jax.jit(_norms)
    delta_norms = jax.jit(lambda master, k: _norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), master,
        ref.init_params(ctx.cell.config, k))))

    def jit_step(state_sh):
        fn = orig_jit(state_sh)
        if ctx.fault == "state_unchanged":
            plain = jax.jit(make_train_step(trainer.model, trainer.opt_cfg))

            def fn(st, batch):  # noqa: F811 - the fault replaces the step
                return st, plain(st, batch)[1]
        calls = [0]

        def step(st, batch):
            i = calls[0]
            calls[0] += 1
            if ctx.fault == "half_batch":
                batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            if i == 0:
                cap["step_call"] = (fn, jax.tree.map(_abstract, (st, batch)))
            with ctx.spans.span("step"):
                new, metrics = fn(st, batch)
            if i == 0:
                # m after one step is (1 - b1) * clip * g: the gradient the
                # optimizer was given, with the clip its reported norm set
                gnorm = float(metrics["grad_norm"])
                clip = min(1.0, opt.grad_clip / max(gnorm, 1e-9))
                scale = np.float32(1.0 / ((1 - opt.b1) * clip))
                cap["grad1"] = np.asarray(norms(new["opt"]["m"])) * scale
                cap["grad1_leaves"] = [np.asarray(x) * scale
                                       for x in jax.tree.leaves(new["opt"]["m"])]
                tree_digest(new)         # compiled here, in set-up
            if i == 2:
                cap["delta3"] = np.asarray(delta_norms(new["opt"]["master"], key))
            if save and i == W:
                cap["saved_digest"] = tree_digest(new)
            return new, metrics
        return step

    trainer._jit_step = jit_step

    if mgr is not None:
        orig_sa, orig_save = mgr.save_async, mgr.save

        def save_async(step, tree, extra=None, delta=False):
            cap["save_call"] = time.perf_counter()
            with ctx.spans.span("save_async"):
                return orig_sa(step, tree, extra, delta=delta)

        def save_bg(step, tree, extra=None, delta=False):
            if ctx.fault == "ckpt_altered":
                tree = _flip_one_bit(tree)
            try:
                return orig_save(step, tree, extra, delta=delta)
            finally:
                cap["save_end"] = time.perf_counter()

        mgr.save_async, mgr.save = save_async, save_bg

    try:
        trainer.fit()
        raise RuntimeError("fit returned before the window closed")
    except WindowClosed:
        pass
    finally:
        win.stop_trace()
    if mgr is not None:
        mgr.wait_pending()
    ctx.out["peak_bytes_in_use"] = _peak_bytes(ctx.chips)
    ctx.out["step_memory_bytes"] = _step_memory_bytes(*cap["step_call"])
    ctx.out["memory_peak_bytes"] = max(
        (b for b in (ctx.out["peak_bytes_in_use"], ctx.out["step_memory_bytes"])
         if b is not None), default=None)
    loader.close()
    fa.shutdown()

    n = cap["window_steps"]
    wall = ctx.window[1] - ctx.window[0]
    ctx.out["attempted"] = n
    ctx.out["e2e"] = {"train_tokens_per_s": n * B * S / wall}
    ctx.out["window_steps"] = n
    ctx.out["tokens_per_step"] = B * S
    if save:
        ctx.out["e2e"]["save_stall_s"] = trainer.ckpt_wait_s / trainer.ckpt_saves
        ctx.out["e2e"]["save_commit_s"] = cap["save_end"] - cap["save_call"]
    losses = [ev.loss for ev in trainer.events[:3]]
    del trainer
    gc.collect()

    # -- what the window produced, against the reference
    wrong = 0
    for g, b in batches.items():
        e, s = divmod(g, spe)
        want = bdata.expected_batch(tokens, ctx.seed, B, e, s)
        if not (np.array_equal(b["tokens"], want[:, :-1])
                and np.array_equal(b["labels"], want[:, 1:])):
            wrong += 1
    ctx.checks["batches_wrong"] = (wrong, 0)
    ctx.out["failed"] = wrong
    if save:
        _check_saved(ctx, cap["saved_digest"])
    first = [(batches[i]["tokens"], batches[i]["labels"]) for i in range(3)]
    _check_training(ctx, ref, key, losses, cap, first)


def _check_saved(ctx: RunContext, saved_digest) -> None:
    """The committed save against the state it was given, leaf by leaf:
    CRC of the bytes read back against the manifest, and the digest of
    those bytes against the digest the device took of the state."""
    import zlib

    from repro.checkpoint import CheckpointManager
    from repro.core import Foreactor, OSDevice

    device = OSDevice()
    fa = Foreactor(device=device, backend="io_uring", depth=32)
    mgr = CheckpointManager(device, f"{ctx.work}/ckpt", fa=fa, num_shards=4)
    try:
        steps = mgr.committed_steps()
        if not steps:
            ctx.checks["ckpt_leaves_wrong"] = (len(np.asarray(saved_digest)), 0)
            return
        manifest = mgr.read_manifest(steps[-1])["leaves"]
        flat, _ = mgr.restore(steps[-1], check_crc=False)
        arrays = [flat[m["name"]] for m in manifest]
        crc_wrong = sum(zlib.crc32(np.ascontiguousarray(a)) != m["crc32"]
                        for a, m in zip(arrays, manifest))
        got = host_digests(arrays)
        want = np.asarray(saved_digest)
        leaves_wrong = int(np.sum(np.any(got != want, axis=1))) \
            if got.shape == want.shape else len(arrays)
    finally:
        fa.shutdown()
    ctx.checks["ckpt_crc_wrong"] = (int(crc_wrong), 0)
    ctx.checks["ckpt_leaves_wrong"] = (leaves_wrong, 0)


def _check_training(ctx, ref, key, losses, cap, first) -> None:
    from .compare import train_gaps

    refr = ref.train_readings(ctx.cell.config, key, first,
                              grad1_of=cap.pop("grad1_leaves"),
                              keep_grad1=ctx.keep_grad1)
    prog = {"losses": np.asarray(losses), "grad1": cap["grad1"],
            "delta3": cap["delta3"]}
    ctx.out["reference_readings"] = refr
    ctx.out["program_readings"] = prog
    ctx.out["first_batches"] = first
    gaps = train_gaps(prog, refr, refr["grad1_diff"])
    ctx.out["loss_gap"] = gaps.pop("loss_gap")     # read, not compared
    ctx.out["leaves_left_out"] = gaps.pop("leaves_left_out")
    limits = ctx.cell.config["limits"]
    for name, value in gaps.items():
        ctx.checks[name] = (value, limits[name])


# -- resume ------------------------------------------------------------------------
def run_resume(ctx: RunContext) -> None:
    from repro.checkpoint import CheckpointManager
    from repro.core import Foreactor, OSDevice
    from repro.launch.steps import make_train_state
    from repro.runtime import Trainer, TrainerConfig
    from jax.sharding import NamedSharding, PartitionSpec

    t = ctx.cell.traffic
    model, ref = build_model(ctx.cell)
    opt = opt_config(ctx.cell)
    mesh = host_mesh(ctx.chips)
    device = OSDevice()
    fa = Foreactor(device=device, backend="io_uring", depth=32)
    loader = _loader(ctx, device, fa, _tokens(ctx))
    ckpt_dir = f"{ctx.work}/ckpt"
    step = int(t["checkpoint_step"])
    key = jax.random.PRNGKey(ctx.seed)

    like = jax.eval_shape(lambda r: make_train_state(model, opt, r), key)
    ctx.out["train_state_bytes"] = _state_bytes(like)
    state = jax.jit(lambda k: ref.train_state(ctx.cell.config, k),
                    out_shardings=NamedSharding(mesh, PartitionSpec()))(key)
    if jax.tree.structure(state) != jax.tree.structure(like):
        raise ValueError("the reference's train state does not have the "
                         "system's layout")
    want = np.asarray(tree_digest(state))
    mgr = CheckpointManager(device, ckpt_dir, fa=fa, num_shards=4)
    mgr.save(step, state, extra={"epoch": 0, "step": step})
    del state
    bdata.evict(ckpt_dir)

    def one_resume() -> Dict[str, Any]:
        t0 = time.perf_counter()
        dev = OSDevice()
        rfa = Foreactor(device=dev, backend="io_uring", depth=32)
        rmgr = CheckpointManager(dev, ckpt_dir, fa=rfa, num_shards=4)
        orig = rmgr.restore_latest

        def restore_latest(like=None):
            with ctx.spans.span("restore_latest"):
                out = orig(like)
            if ctx.fault == "restore_lowered" and out is not None:
                # the control: float32 leaves brought back through bfloat16
                s, tree, extra = out
                out = (s, jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
                    if x.dtype == np.float32 else x, tree), extra)
            if ctx.fault == "restore_altered" and out is not None:
                out = (out[0], _flip_one_bit(out[1]), out[2])
            return out

        rmgr.restore_latest = restore_latest
        tr = Trainer(model, opt, loader, rmgr, mesh,
                     TrainerConfig(steps=step, ckpt_every=0, log_every=0,
                                   seed=ctx.seed, restore=True))
        res = tr.fit()
        jax.block_until_ready(res["state"])
        t1 = time.perf_counter()
        got = tree_digest(res["state"])
        ok = tr.restored_step == step and np.array_equal(np.asarray(got), want)
        del res, tr
        rfa.shutdown()
        st = dict(vars(rfa.total_stats))
        bdata.evict(ckpt_dir)
        return {"seconds": t1 - t0, "ok": ok, "stats": st}

    one_resume()                                   # warm-up, not timed
    win = _Window(ctx)
    runs: List[Dict[str, Any]] = []
    win.open()
    try:
        while not runs or time.perf_counter() - win.t0 < ctx.seconds:
            runs.append(one_resume())
        win.close()
    finally:
        win.stop_trace()
    ctx.out["memory_peak_bytes"] = ctx.out["peak_bytes_in_use"] = \
        _peak_bytes(ctx.chips)
    loader.close()
    fa.shutdown()

    ctx.out["attempted"] = len(runs)
    ctx.out["failed"] = sum(not r["ok"] for r in runs)
    ctx.out["e2e"] = {"resume_s": float(np.mean([r["seconds"] for r in runs]))}
    ctx.out["resumes"] = [r["seconds"] for r in runs]
    ctx.stats["fa_delta"] = {
        k: sum(r["stats"][k] for r in runs)
        for k in runs[0]["stats"] if isinstance(runs[0]["stats"][k], (int, float))}
    ctx.checks["resumes_wrong"] = (ctx.out["failed"], 0)


DRIVERS = {"train": run_train, "resume": run_resume}


def clean(work: str) -> None:
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)


def clean_stale(root: str) -> None:
    """Remove the scratch of runs that ended without removing their own
    (``<name>.<pid>`` of a process that no longer exists)."""
    if not os.path.isdir(root):
        return
    for name in os.listdir(root):
        pid = name.rsplit(".", 1)[-1]
        if pid.isdigit() and not os.path.exists(f"/proc/{pid}"):
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
