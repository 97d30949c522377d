"""End-to-end training runtime tests: loss goes down, checkpoint restart
is bit-deterministic with the continuous run, straggler accounting."""

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import MemDevice
from repro.data import DataConfig, ShardedTokenDataset, TokenBatchLoader, write_synthetic_dataset
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


def setup(steps=12, ckpt_every=0, root="/ck", dev=None, schedule_steps=None):
    dev = dev or MemDevice()
    cfg = get_config("tinyllama_1_1b", smoke=True)
    dcfg = DataConfig(seq_len=32, batch_size=4, seed=5)
    write_synthetic_dataset(dev, "/data", dcfg, 2, 24, vocab_size=cfg.vocab_size)
    ds = ShardedTokenDataset(dev, [f"/data/shard_{i:05d}.rio" for i in range(2)])
    loader = TokenBatchLoader(ds, dcfg, prefetch=False)
    model = build_model(cfg)
    # schedule_steps pins the LR schedule independently of how far this
    # (possibly interrupted) run goes — matching production restarts.
    opt = AdamWConfig(lr=1e-3, warmup_steps=2,
                      total_steps=schedule_steps or steps, grad_clip=1.0)
    ckpt = CheckpointManager(dev, root, num_shards=2, chunk_bytes=1 << 14) \
        if ckpt_every else None
    tcfg = TrainerConfig(steps=steps, ckpt_every=ckpt_every, log_every=0)
    return dev, Trainer(model, opt, loader, ckpt, make_host_mesh(), tcfg)


def test_loss_decreases():
    _, tr = setup(steps=15)
    out = tr.fit()
    losses = out["losses"]
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    assert out["final_step"] == 15


def test_checkpoint_restart_is_deterministic():
    # continuous 12-step run
    dev1, tr1 = setup(steps=12, ckpt_every=50, root="/ck1")
    out1 = tr1.fit()
    # interrupted run: 6 steps, checkpoint, then resume to 12
    dev2, tr2 = setup(steps=6, ckpt_every=6, root="/ck2", schedule_steps=12)
    tr2.fit()
    dev2b, tr2b = setup(steps=12, ckpt_every=50, root="/ck2", dev=dev2)
    out2 = tr2b.fit()
    # identical final params
    p1 = jax.tree.leaves(out1["state"]["params"])
    p2 = jax.tree.leaves(out2["state"]["params"])
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_emergency_checkpoint_on_crash():
    dev, tr = setup(steps=50, ckpt_every=100, root="/ck")
    calls = {"n": 0}
    orig_load = tr.loader.load

    def exploding_load(e, s):
        calls["n"] += 1
        if calls["n"] > 5:
            raise RuntimeError("node failure!")
        return orig_load(e, s)

    tr.loader.load = exploding_load
    with pytest.raises(RuntimeError, match="node failure"):
        tr.fit()
    assert tr.ckpt.latest_step() is not None  # emergency save landed
    # and it restores
    out = tr.ckpt.restore_latest()
    assert out is not None


def test_failed_emergency_save_raises_checkpoint_error():
    from repro.checkpoint import CheckpointError

    _, tr = setup(steps=50, ckpt_every=100, root="/ck")
    orig_load = tr.loader.load

    def exploding_load(e, s):
        if s >= 3:
            raise RuntimeError("node failure!")
        return orig_load(e, s)

    def failing_save(*a, **kw):
        raise OSError("disk gone")

    tr.loader.load = exploding_load
    tr.ckpt.save = failing_save
    with pytest.raises(CheckpointError, match="emergency save at step 3") as ei:
        tr.fit()
    # the node failure that triggered the save is kept as the context
    assert "node failure" in repr(ei.value.__context__.__context__)
    assert tr.summary()["emergency_step"] is None


def test_resume_reports_restored_step_and_does_not_resave():
    dev, tr = setup(steps=6, ckpt_every=3, root="/ck")
    out = tr.fit()
    assert out["restored_step"] is None and out["final_step"] == 6
    assert out["ckpt_saves"] == 2  # steps 3 and 6; the final save is step 6's
    _, tr2 = setup(steps=6, ckpt_every=3, root="/ck", dev=dev)
    back = tr2.fit()
    assert back["restored_step"] == 6 and back["losses"] == []
    assert back["ckpt_saves"] == 0
    for a, b in zip(jax.tree.leaves(out["state"]), jax.tree.leaves(back["state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
