"""The program-span reduction and the readers built on it, on hand-made
span and operation lists: nesting by thread, clipping to the window,
work matched across threads by its ``step`` key, and device idle time
inside a background save."""

from types import SimpleNamespace

import pytest

import run as bench_run
from bench import program_spans as ps
from bench.spec import BENCH_DIR
from bench.trace import from_events

T, S, P = "/host:CPU/0", "/host:CPU/1", "/host:CPU/2"   # training, save, prefetch

TRAIN_SAVE = [
    ("trainer.step", 0, 10, T, {"step": 5}),
    ("trainer.load", 0, 2, T, {"step": 5}),
    ("data.load", 0, 2, T, {"epoch": 0, "step": 5}),
    ("data.wait", 0.5, 1.5, T, {"epoch": 0, "step": 5}),
    ("data.read", 0.2, 1.4, P, {"epoch": 0, "step": 5, "bytes": 64}),
    ("trainer.step", 10, 20, T, {"step": 6}),
    ("trainer.load", 10, 11, T, {"step": 6}),
    ("data.load", 10, 11, T, {"epoch": 0, "step": 6}),
    ("data.wait", 10, 10.25, T, {"epoch": 0, "step": 6}),
    ("trainer.save", 15, 19, T, {"step": 6}),
    ("ckpt.save_async", 15, 19, T, {"step": 6, "bytes": 100}),
    ("ckpt.join", 15, 15.5, T, {"step": 6}),
    ("ckpt.snapshot", 15.5, 19, T, {"step": 6}),
    # the save the call handed to its thread
    ("ckpt.save", 19.5, 60, S, {"step": 6, "bytes": 100, "kind": "full"}),
    ("ckpt.plan", 19.5, 20, S, {"step": 6}),
    ("ckpt.write", 20, 55, S, {"step": 6}),
    ("fa.session", 20, 55, S, {"graph": "ckpt_save", "wait_s": 3.0, "sync_s": 1.0}),
    ("ckpt.serialize", 21, 22, S, {"leaf": 0, "bytes": 50}),
    ("ckpt.crc", 50, 54, S, {"step": 6}),
    ("ckpt.gc", 55, 59, S, {"step": 6}),
    ("fa.session", 56, 57, S, {"graph": "ckpt_gc", "wait_s": 100.0, "sync_s": 0.0}),
    # a later call that blocks on the join while the device idles
    ("ckpt.save_async", 45, 47, T, {"step": 8}),
    ("ckpt.join", 45, 47, T, {"step": 8}),
    # a synchronous save on the training thread is not handed off
    ("ckpt.save", 70, 71, T, {"step": 8}),
    ("ckpt.crc", 70.2, 70.4, T, {"step": 8}),
]
#: busy except [15, 19) (the stall), [30, 40) (the commit), [45, 47)
BUSY = [("op", 0.0, 15.0), ("op", 19.0, 11.0), ("op", 40.0, 5.0),
        ("op", 47.0, 53.0)]

RESUME = []
for t0 in (1.0, 21.0):
    RESUME += [
        ("trainer.restore", t0, t0 + 10, T, {"step": 4, "bytes": 4e9}),
        ("ckpt.restore", t0, t0 + 8, T, {"step": 4, "bytes": 4e9}),
        ("ckpt.discover", t0, t0 + 0.5, T, {}),
        ("fa.session", t0 + 0.1, t0 + 0.2, T, {"graph": "stat_list"}),
        ("ckpt.read", t0 + 0.5, t0 + 4.5, T, {"step": 4, "bytes": 4e9}),
        ("fa.session", t0 + 0.5, t0 + 4.5, T, {"graph": "pread_extents"}),
        ("ckpt.overlay", t0 + 4.5, t0 + 6, T, {"step": 4}),
        ("ckpt.crc", t0 + 6, t0 + 8, T, {"step": 4}),
        ("trainer.place", t0 + 8, t0 + 10, T, {"step": 4}),
    ]


def ctx_of(events, monkeypatch, ops=(), window=(0.0, 100.0), **out):
    monkeypatch.setattr(ps, "read_events", lambda trace_dir: events)
    tr = from_events({"/device:TPU:0": list(ops)}, [("window", *window)])
    return SimpleNamespace(trace_dir="trace", trace_data=tr, out=out,
                           stats={}, window=window, trace=True)


def reader(name):
    return bench_run.load_metric_reader(BENCH_DIR, name)


def test_spans_nest_on_their_own_thread():
    spans = ps.nest(TRAIN_SAVE)
    by = {(s.name, s.start): s for s in spans}
    assert by[("data.wait", 0.5)].parent is by[("data.load", 0)]
    assert by[("data.load", 0)].parent is by[("trainer.load", 0)]
    assert by[("trainer.load", 0)].parent is by[("trainer.step", 0)]
    # on another thread, inside in time only: no parent
    assert by[("data.read", 0.2)].parent is None
    assert by[("ckpt.save", 19.5)].parent is None
    crc = by[("ckpt.crc", 50)]
    assert crc.parent is by[("fa.session", 20)]
    assert crc.under(by[("ckpt.write", 20)]) and crc.under(by[("ckpt.save", 19.5)])
    assert not crc.under(by[("ckpt.save_async", 15)])
    assert [s.start for s in spans] == sorted(s.start for s in spans)


def test_clipping_to_the_window():
    events = [("outer", -5, 8, T, {}), ("inner", -3, 8, T, {}),
              ("before", -4, -1, T, {}), ("late", 9, 20, T, {}),
              ("after", 12, 13, T, {})]
    spans = ps.nest(events, window=(0.0, 10.0))
    by = {s.name: s for s in spans}
    assert set(by) == {"outer", "inner", "late"}    # wholly outside: dropped
    assert (by["late"].start, by["late"].end) == (9.0, 10.0)
    # clipped alike to [0, 8), the span that began first stays the outer
    assert (by["outer"].start, by["outer"].end) == (0.0, 8.0)
    assert (by["inner"].start, by["inner"].end) == (0.0, 8.0)
    assert by["inner"].parent is by["outer"] and by["outer"].parent is None


def test_work_handed_to_another_thread_is_matched_by_step():
    spans = ps.nest(TRAIN_SAVE)
    saves = ps.handed_off(spans, "ckpt.save", "ckpt.save_async")
    assert [(s.meta["step"], s.thread) for s in saves] == [(6, S)]
    # a step no call carries, or a save on the caller's own thread, is not
    stray = ps.nest(TRAIN_SAVE + [("ckpt.save", 80, 81, S, {"step": 9})])
    assert len(ps.handed_off(stray, "ckpt.save", "ckpt.save_async")) == 1


def test_idle_seconds_on_hand_made_gaps():
    ops = [(0, 2), (3, 5), (4, 6), (8, 10)]
    assert ps.idle_s(ops, 0, 10) == pytest.approx(3.0)      # [2,3) and [6,8)
    assert ps.idle_s(ops, 1, 9) == pytest.approx(3.0)
    assert ps.idle_s(ops, 0, 10, minus=[(5, 7)]) == pytest.approx(2.0)
    assert ps.idle_s(ops, 0, 10, minus=[(-1, 11)]) == 0.0
    assert ps.idle_s([], 2, 4, minus=[(3, 3.5), (3.2, 3.8)]) == pytest.approx(1.2)


def test_train_save_readers(monkeypatch):
    ctx = ctx_of(TRAIN_SAVE, monkeypatch, ops=BUSY, window_steps=2)
    assert reader("prefetch_wait_ms.save")(ctx) == pytest.approx(625.0)
    assert reader("save_snapshot_s")(ctx) == pytest.approx(3.5)
    assert reader("save_crc_s")(ctx) == pytest.approx(4.0)
    assert reader("save_io_wait_s")(ctx) == pytest.approx(4.0)
    # inside [19.5, 60): idle [30, 40) and [45, 47), the latter a stall of
    # the training thread's own
    assert reader("commit_idle_s")(ctx) == pytest.approx(10.0)


def test_commit_idle_is_a_mean_over_chips(monkeypatch):
    ctx = ctx_of(TRAIN_SAVE, monkeypatch)
    ctx.trace_data = from_events(
        {"/device:TPU:0": BUSY, "/device:TPU:1": [("op", 0.0, 100.0)]},
        [("window", 0.0, 100.0)])
    assert reader("commit_idle_s")(ctx) == pytest.approx(5.0)


def test_resume_readers(monkeypatch):
    ctx = ctx_of(RESUME, monkeypatch)
    ctx.stats["fa_delta"] = {"intercepted": 1000, "peek_seconds": 0.1,
                             "harvest_seconds": 0.4, "served_async": 990}
    assert reader("restore_read_MB_per_s")(ctx) == pytest.approx(1000.0)
    assert reader("restore_copy_s")(ctx) == pytest.approx(1.5)
    assert reader("restore_crc_s")(ctx) == pytest.approx(2.0)
    assert reader("restore_place_s")(ctx) == pytest.approx(2.0)
    assert reader("engine_us_per_intercept.resume")(ctx) == pytest.approx(500.0)


NEW = ["prefetch_wait_ms", "prefetch_wait_ms.save", "save_snapshot_s",
       "save_crc_s", "save_io_wait_s", "commit_idle_s",
       "restore_read_MB_per_s", "restore_copy_s", "restore_crc_s",
       "restore_place_s"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    ctx = ctx_of([], monkeypatch, ops=BUSY, window_steps=2)
    assert reader(name)(ctx) is None


def test_readers_of_an_untraced_context_read_nothing():
    ctx = SimpleNamespace(trace_dir=None, trace_data=None, out={}, stats={})
    assert ps.window_spans(ctx) == []
    assert reader("engine_us_per_intercept.resume")(ctx) is None
