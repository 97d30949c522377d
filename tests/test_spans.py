"""The program's spans in a profiler trace: a tiny training run with
write-behind saves and a resume, traced, gives the span tree the trace
readers rely on; without jax loaded a span is a no-op."""

import glob
import os
import subprocess
import sys

import jax
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import MemDevice
from repro.data import DataConfig, ShardedTokenDataset, TokenBatchLoader, write_synthetic_dataset
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime import Trainer, TrainerConfig
from repro.spans import PREFIX

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")


class Span:
    def __init__(self, name, start, end, thread, meta):
        self.name, self.start, self.end = name, start, end
        self.thread, self.meta, self.parent = thread, meta, None

    def ancestors(self):
        p = self.parent
        while p is not None:
            yield p
            p = p.parent


def read_spans(trace_dir):
    """Every ``repro:`` span of the trace, each linked to the innermost
    span containing it on its own thread (host line)."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      (plane.name, li), dict(e.stats)))
    spans.sort(key=lambda s: (s.thread, s.start, -s.end))
    stack = []
    for s in spans:
        while stack and not (stack[-1].thread == s.thread
                             and s.end <= stack[-1].end):
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(s)
    return spans


def _trainer(dev, steps, restore):
    cfg = get_config("tinyllama_1_1b", smoke=True)
    dcfg = DataConfig(seq_len=32, batch_size=4, seed=5)
    ds = ShardedTokenDataset(dev, [f"/data/shard_{i:05d}.rio" for i in range(2)])
    loader = TokenBatchLoader(ds, dcfg)            # with the prefetch thread
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps, grad_clip=1.0)
    ckpt = CheckpointManager(dev, "/ck", num_shards=2, chunk_bytes=1 << 14)
    tcfg = TrainerConfig(steps=steps, ckpt_every=2, log_every=0,
                         restore=restore, write_behind=True)
    return Trainer(build_model(cfg), opt, loader, ckpt, make_host_mesh(), tcfg)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The spans of a traced fit with two write-behind saves, then of a
    resume from the last, and the resumed trainer's ``restore_s``."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    dev = MemDevice()
    cfg = get_config("tinyllama_1_1b", smoke=True)
    write_synthetic_dataset(dev, "/data", DataConfig(seq_len=32, batch_size=4),
                            2, 24, vocab_size=cfg.vocab_size)
    with jax.profiler.trace(trace_dir):
        tr = _trainer(dev, steps=4, restore=False)
        tr.fit()
        tr.loader.close()
        again = _trainer(dev, steps=4, restore=True)
        again.fit()
        again.loader.close()
    assert again.restored_step == 4
    return read_spans(trace_dir), again.restore_s


@pytest.fixture(scope="module")
def spans(traced):
    return traced[0]


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_step_contains_load_put_compute_save(spans):
    steps = named(spans, "trainer.step")
    assert [s.meta["step"] for s in steps] == [0, 1, 2, 3]
    for kid in ("trainer.load", "trainer.put", "trainer.compute"):
        got = named(spans, kid)
        assert len(got) == 4 and all(s.parent.name == "trainer.step" for s in got)
    saves = named(spans, "trainer.save")
    assert sorted(s.meta["step"] for s in saves) == [2, 4]
    assert all(s.parent.name == "trainer.step" for s in saves)
    # the loader's span inside the trainer's, the wait for the prefetch
    # thread inside the loader's
    loads = named(spans, "data.load")
    assert loads and all(s.parent.name == "trainer.load" for s in loads)
    waits = named(spans, "data.wait")
    assert waits and all(s.parent.name == "data.load" for s in waits)


def test_prefetch_reads_run_on_their_own_thread(spans):
    main = named(spans, "trainer.step")[0].thread
    bg = [s for s in named(spans, "data.read") if s.thread != main]
    assert bg and all(s.parent is None and s.meta["bytes"] == 4 * 33 * 4
                      for s in bg)
    loaded = {(s.meta["epoch"], s.meta["step"]) for s in named(spans, "data.load")}
    assert {(s.meta["epoch"], s.meta["step"]) for s in bg} & loaded


def test_save_runs_on_another_thread_with_the_callers_step(spans):
    calls = named(spans, "ckpt.save_async")
    assert sorted(s.meta["step"] for s in calls) == [2, 4]
    for call in calls:
        kids = {s.name for s in spans if s.parent is call}
        assert kids == {"ckpt.join", "ckpt.snapshot"}
        assert call.meta["bytes"] > 0 and call.parent.name == "trainer.save"
        save, = [s for s in named(spans, "ckpt.save")
                 if s.meta["step"] == call.meta["step"]]
        assert save.thread != call.thread and save.parent is None
        assert save.meta["kind"] == "full" and save.meta["bytes"] == call.meta["bytes"]
        kids = {s.name for s in spans if s.parent is save}
        assert kids == {"ckpt.plan", "ckpt.write", "ckpt.gc"}
        under = {s.name for s in spans if save in s.ancestors()}
        assert {"ckpt.serialize", "ckpt.crc", "fa.session"} <= under


def test_restore_splits_into_discover_read_overlay_crc_and_place(traced):
    spans, restore_s = traced
    top, = named(spans, "trainer.restore")
    # the trainer's restore time ends once the placed state is ready
    assert restore_s >= (top.end - top.start) * 1e-9 > 0
    assert top.meta["step"] == 4 and top.meta["bytes"] > 0
    assert [s.name for s in spans if s.parent is top] == \
        ["ckpt.restore", "trainer.place"]
    restore, = named(spans, "ckpt.restore")
    assert restore.meta["bytes"] == top.meta["bytes"]
    kids = [s.name for s in spans if s.parent is restore]
    assert kids[0] == "ckpt.discover"
    assert {"ckpt.read", "ckpt.overlay", "ckpt.crc"} <= set(kids)
    read, = [s for s in spans if s.name == "ckpt.read" and s.parent is restore]
    assert read.meta["bytes"] == restore.meta["bytes"]


def test_engine_sessions_carry_their_counters(spans):
    restore, = named(spans, "ckpt.restore")
    read, = [s for s in spans if s.name == "ckpt.read" and s.parent is restore]
    sessions = [s for s in spans if s.parent is read]
    assert {s.meta["graph"] for s in sessions} == {"open_list", "pread_extents"}
    for s in sessions:
        assert s.name == "fa.session" and s.meta["intercepted"] > 0
        assert {"served_async", "pre_issued", "wait_s", "sync_s", "peek_s",
                "harvest_s"} <= set(s.meta)


def test_core_imports_without_jax_and_its_spans_do_nothing():
    code = ("import sys\n"
            "import repro.core\n"
            "from repro.spans import span\n"
            "with span('x', step=1) as sp:\n"
            "    assert not sp.is_enabled()\n"
            "    sp.set_metadata(bytes=1)\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH=SRC),
                       timeout=120)
    assert r.returncode == 0, r.stderr
