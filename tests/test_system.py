"""End-to-end behaviour of the paper's system: explicit speculation
overlaps an I/O loop's syscalls on a parallel device while preserving
results (the paper's core claims, scaled to CI).

Overlap is read from the device's own counters, not from wall time: the
serial run never has two operations in flight, and the speculated run has
more than one in flight and serves pre-issued results.  A fresh
``SimulatedDevice`` wraps the same ``MemDevice`` for each run, because
``max_inflight`` is a running maximum."""

import numpy as np

from repro.core import DeviceProfile, Foreactor, MemDevice, SimulatedDevice
from repro.store import plugins
from repro.store.fileutils import du_dir
from repro.store.lsm import LSMTree

FAST = DeviceProfile(channels=16, base_latency=8e-4, metadata_latency=6e-4,
                     crossing_cost=3e-6)


def test_speculation_speeds_up_stat_loop():
    """Fig. 6(a) mechanism: du with pre-issuing overlaps its stats, where
    serial du issues them one at a time, and the result is identical."""
    inner = MemDevice()
    for i in range(80):
        fd = inner.open(f"/d/f{i}", "w")
        inner.pwrite(fd, b"z" * (i + 1), 0)
        inner.close(fd)
    serial = SimulatedDevice(inner, FAST)
    expect = du_dir(serial, "/d")
    assert serial.stats.max_inflight == 1

    dev = SimulatedDevice(inner, FAST)
    fa = Foreactor(device=dev, backend="io_uring", depth=16)
    plugins.register_all(fa)
    wrapped = fa.wrap("du", plugins.capture_du)(du_dir)
    assert wrapped(dev, "/d") == expect
    assert dev.stats.max_inflight > 1, dev.stats.snapshot()
    assert fa.total_stats.served_async > 0
    fa.shutdown()


def test_speculation_speeds_up_lsm_get():
    """Fig. 8 mechanism: Get over a multi-table chain overlaps its table
    reads with speculation, identical results, early exit preserved."""
    rng = np.random.default_rng(0)
    inner = MemDevice()
    lsm = LSMTree(inner, "/db", memtable_limit_bytes=1 << 13, l0_limit=100,
                  fsync_writes=False)
    ref = {}
    for k in rng.permutation(1500):
        v = f"{k:08d}".encode() * 4
        lsm.put(int(k), v)
        ref[int(k)] = v
    lsm.flush()
    assert lsm.table_count() >= 4
    keys = [int(k) for k in rng.choice(1500, 40)]

    serial = SimulatedDevice(inner, FAST)
    lsm_serial = LSMTree.open_existing(serial, "/db")
    for k in keys:
        assert lsm_serial.get(k) == ref[k]
    assert serial.stats.max_inflight == 1

    dev = SimulatedDevice(inner, FAST)
    lsm_sim = LSMTree.open_existing(dev, "/db")
    fa = Foreactor(device=dev, backend="io_uring", depth=16)
    plugins.register_all(fa)
    get = fa.wrap("lsm_get", plugins.capture_lsm_get)(lambda l, k: l.get(k))
    for k in keys:
        assert get(lsm_sim, k) == ref[k]
    assert dev.stats.max_inflight > 1, dev.stats.snapshot()
    assert fa.total_stats.served_async > 0
    fa.shutdown()


def test_backend_swap_preserves_semantics():
    """Table 1: the same graphs run unmodified on both backends."""
    inner = MemDevice()
    for i in range(30):
        fd = inner.open(f"/d/f{i}", "w")
        inner.pwrite(fd, b"y" * (i + 1), 0)
        inner.close(fd)
    results = {}
    for backend in ("io_uring", "user_threads", "sync"):
        dev = SimulatedDevice(inner, FAST)
        fa = Foreactor(device=dev, backend=backend, depth=8)
        plugins.register_all(fa)
        wrapped = fa.wrap("du", plugins.capture_du)(du_dir)
        results[backend] = wrapped(dev, "/d")
        fa.shutdown()
    assert len(set(results.values())) == 1
