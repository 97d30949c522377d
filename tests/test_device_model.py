"""Device cost-model regressions: the bandwidth-vs-request-size curve and
the request-size-aware page-cache hit cost.

The extent coalescer's entire win rests on the cost shape
``base_latency + size * per_byte``: amortizing one command setup over an
MB-scale super-read.  These tests pin that curve analytically (no
sleeping) and pin the page-cache hit model — a cache hit charges
``cache_hit_latency + size * cache_hit_per_byte`` (the kernel memcpy out
of the cache scales with request size; a 1 MB cached read is not free the
way a 1 KB one nearly is) and occupies no device channel.
"""

import pytest

import repro.core.device as device_mod
from repro.core import DeviceProfile, MemDevice, SimulatedDevice

#: the paper's Toshiba NVMe (~60 MB/s @ QD1/4 KB => ~66 us/op; ~1.2 GB/s
#: peak): the curve the two bandwidth tests pin
NVME = DeviceProfile(
    channels=16,
    base_latency=60e-6,
    per_byte=1.2e-9,
    crossing_cost=2.5e-6,
    metadata_latency=40e-6,
)


def model_bandwidth(profile: DeviceProfile, size: int) -> float:
    """Per-channel streaming bandwidth (bytes/s) at one request size."""
    return size / (profile.base_latency + size * profile.per_byte)


def test_bandwidth_curve_is_monotone_in_request_size():
    sizes = [1 << k for k in range(9, 23)]  # 512 B .. 4 MiB
    bws = [model_bandwidth(NVME, s) for s in sizes]
    assert bws == sorted(bws)


def test_nvme_profile_curve_endpoints_pinned():
    # the coalescing win: ~17 MB/s at 1 KiB requests vs ~800 MB/s at
    # 1 MiB super-reads, per channel
    assert model_bandwidth(NVME, 1 << 10) == pytest.approx(
        16.7e6, rel=0.05)
    assert model_bandwidth(NVME, 1 << 20) == pytest.approx(
        795e6, rel=0.05)


def test_raw_bandwidth_ceiling():
    p = DeviceProfile(channels=4, per_byte=4e-9)
    assert p.raw_bandwidth_bytes() == pytest.approx(1e9)
    assert DeviceProfile(per_byte=0.0).raw_bandwidth_bytes() == float("inf")
    # the per-channel curve approaches (never exceeds) the raw ceiling
    assert model_bandwidth(p, 1 << 30) < 1e9 / p.channels * 1.001


class _SleepRecorder:
    def __init__(self):
        self.durations = []

    def __call__(self, dur):
        self.durations.append(dur)


@pytest.fixture()
def recorded_sleep(monkeypatch):
    rec = _SleepRecorder()
    monkeypatch.setattr(device_mod, "_precise_sleep", rec)
    return rec


def _dev(cache_bytes=1 << 20, **profile_kw):
    profile = DeviceProfile(**profile_kw)
    inner = MemDevice()
    fd = inner.open("/f", "w")
    inner.pwrite(fd, bytes(range(256)) * 8192, 0)  # 2 MiB
    inner.close(fd)
    dev = SimulatedDevice(inner, profile, cache_bytes=cache_bytes)
    return dev, dev.open("/f", "r")


def test_cache_hit_cost_accounts_for_request_size(recorded_sleep):
    """Regression for the flat-hit-cost bug: a hit used to charge only the
    fixed latency, making MB-scale cached reads implausibly free.  The hit
    charge is pinned to ``cache_hit_latency + size * cache_hit_per_byte``."""
    p = dict(base_latency=1e-3, per_byte=1e-9,
             cache_hit_latency=5e-6, cache_hit_per_byte=1e-10,
             metadata_latency=0.0)
    dev, fd = _dev(**p)
    recorded_sleep.durations.clear()

    for size in (1 << 10, 1 << 20):
        dev.pread(fd, size, 0)  # miss: full device charge
        assert recorded_sleep.durations[-1] == pytest.approx(
            p["base_latency"] + size * p["per_byte"])
        dev.pread(fd, size, 0)  # hit: kernel copy-out, size-dependent
        assert recorded_sleep.durations[-1] == pytest.approx(
            p["cache_hit_latency"] + size * p["cache_hit_per_byte"])

    hit_1k = p["cache_hit_latency"] + (1 << 10) * p["cache_hit_per_byte"]
    hit_1m = p["cache_hit_latency"] + (1 << 20) * p["cache_hit_per_byte"]
    assert hit_1m > hit_1k  # the curve, not a flat constant


def test_cache_hit_occupies_no_channel(recorded_sleep):
    """Hits must not consume device-channel slots: a single-channel device
    serves cached reads without queueing behind the device."""
    dev, fd = _dev(channels=1, base_latency=1e-3, per_byte=0.0,
                   cache_hit_latency=5e-6, cache_hit_per_byte=1e-10,
                   metadata_latency=0.0)
    dev.pread(fd, 4096, 0)  # warm the cache
    # exhaust the only channel; a hit must still be served
    assert dev._channels.acquire(blocking=False)
    try:
        recorded_sleep.durations.clear()
        dev.pread(fd, 4096, 0)
        assert len(recorded_sleep.durations) == 1  # did not block on _service
        assert recorded_sleep.durations[0] == pytest.approx(
            5e-6 + 4096 * 1e-10)
    finally:
        dev._channels.release()


def test_direct_mode_always_charges_device(recorded_sleep):
    dev, fd = _dev(base_latency=1e-3, per_byte=1e-9, metadata_latency=0.0)
    dev_direct = SimulatedDevice(dev.inner, dev.profile,
                                 cache_bytes=1 << 20, direct=True)
    dfd = dev_direct.open("/f", "r")
    recorded_sleep.durations.clear()
    for _ in range(2):
        dev_direct.pread(dfd, 4096, 0)
    # no cache on the direct lane: both reads pay full device service
    assert recorded_sleep.durations == [
        pytest.approx(1e-3 + 4096 * 1e-9)] * 2
