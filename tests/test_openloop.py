"""Deterministic open-loop scheduler harness (the 10k-session-scale test).

The open-loop server (``repro.launch.ioserver.run_openloop``) drives the
shared backend with wall-clock Poisson arrivals and real server threads —
fit for finding the saturation knee, useless as a CI regression test
(timing noise, sleeps, machine-dependent capacity).  This module replays
the *same seeded arrival traces*
(:func:`repro.launch.ioserver.arrival_schedule`) through the *same
scheduler machinery* (``SlotScheduler`` + ``SharedBackend`` views + the
completion pool) with:

* a :class:`ManualPlane` — an :class:`repro.core.backends.IOPlane` with no
  worker threads: admitted requests queue on a deque and execute only when
  the harness pumps them (a pump is "a worker ran"), demand-promoted chains
  execute inline (they outrank everything, so a real pool would run them
  next anyway);
* a :class:`FakeClock` — virtual time only advances at arrivals, so the
  trace replays identically on every run;
* a seeded interleaver — each step either admits the next arrival (a fresh
  tenant session) or advances one live session by one intercept, with
  pumps in between.  Thousands of sessions are genuinely concurrent
  (attached, holding slots, mid-graph) on a single thread.

Zero wall-clock sleeps anywhere; every schedule decision comes from one
``random.Random(seed)``.  The invariants checked at drain are the ones the
O(1) admission path and the pooled completion primitive must preserve at
scale: no deadlock, ``max_spec_inflight <= capacity``, zero leaked slots,
zero leaked tenants (the deferred-reap path), byte-correct results, and
the session-stats ledger
``pre_issued == served_async + cancelled + wasted_completions``.

Also here: the open-loop utility units (arrival schedule determinism, the
in-flight +1/-1 sweep, the fake clock) and the isolated-mode thread-budget
regression tests (the old code hard-coded 8 workers per client — 64
clients would have spawned 512 threads).
"""

import random

import pytest

from repro.core import MemDevice
from repro.core.backends import IOPlane, SharedBackend, SlotScheduler
from repro.core.engine import SessionStats, SpecSession
from repro.core.patterns import build_pread_extents_graph
from repro.core.syscalls import ReqState, Sys, perform
from repro.launch.ioserver import (ISOLATED_THREAD_BUDGET, FakeClock,
                                   arrival_schedule, isolated_workers,
                                   make_foreactor, max_inflight)


# -- open-loop utility units --------------------------------------------------

def test_arrival_schedule_is_deterministic_and_well_formed():
    a = arrival_schedule(64, 0.5, 2.0, seed=11)
    b = arrival_schedule(64, 0.5, 2.0, seed=11)
    assert a == b, "same seed must replay the identical trace"
    assert a != arrival_schedule(64, 0.5, 2.0, seed=12)
    times = [t for t, _ in a]
    assert times == sorted(times)
    assert all(0.0 <= t < 2.0 for t in times)
    assert [i for _, i in a] == list(range(len(a)))  # sequential session ids
    # superposition: 64 sessions at 0.5/s for 2s ~ 64 arrivals (Poisson)
    assert 20 <= len(a) <= 140


def test_arrival_schedule_zero_rate_is_empty():
    assert arrival_schedule(0, 1.0, 5.0) == []
    assert arrival_schedule(10, 0.0, 5.0) == []


def test_fake_clock_never_goes_backwards():
    c = FakeClock()
    c.advance_to(1.5)
    c.advance_to(0.5)  # stale arrival timestamp: ignored
    assert c.now() == 1.5
    c.advance_to(2.0)
    assert c.now() == 2.0


def test_max_inflight_counts_overlap_not_touching_sessions():
    # [0,2) and [2,4) touch but never overlap; [1,3) overlaps both
    assert max_inflight([(0, 2), (2, 4)]) == 1
    assert max_inflight([(0, 2), (2, 4), (1, 3)]) == 2
    assert max_inflight([(0, 10), (1, 9), (2, 8)]) == 3
    assert max_inflight([]) == 0


# -- isolated-mode thread-budget regression -----------------------------------

def test_isolated_workers_keeps_the_historical_8_client_shape():
    assert isolated_workers(8) == 8  # 8 clients x 8 = the original 64


def test_isolated_workers_never_oversubscribes():
    """The regression: at 64 clients the old per-client constant would have
    spawned 512 worker threads.  The budget split keeps the total near
    ISOLATED_THREAD_BUDGET (the [2,8] clamp allows a small floor excess)."""
    for clients in (1, 2, 4, 8, 16, 32, 64, 128, 256):
        per = isolated_workers(clients)
        assert 2 <= per <= 8
        assert clients * per <= max(ISOLATED_THREAD_BUDGET, 2 * clients), \
            f"{clients} clients x {per} workers oversubscribes"


def test_make_foreactor_isolated_scales_workers_with_clients():
    fa = make_foreactor("isolated", MemDevice(), clients=64)
    try:
        assert fa.workers == isolated_workers(64) == 2
    finally:
        fa.shutdown()


# -- the deterministic scheduler harness --------------------------------------

class ManualPlane(IOPlane):
    """A zero-thread I/O plane: admitted requests queue until the harness
    pumps them; demand-promoted requests (priority stamped past
    ``SharedBackend.DEMAND_BOOST``) execute inline — a real worker pool
    would run them next regardless, they outrank every queued entry."""

    def __init__(self, device):
        super().__init__(device, lanes=())
        self.pending = []
        self.executed = 0

    def _run(self, req) -> None:
        if req.claim():  # skips cancelled/evicted/already-run entries
            req.finish(perform(self.device, req))
            self.executed += 1

    def submit(self, batch):
        if not batch:
            return 0
        with self._lock:
            self._submitted.extend(batch)
            if len(self._submitted) > self._LEDGER_COMPACT:
                self._submitted = [r for r in self._submitted
                                   if not r.is_done()]
        for r in batch:
            if r.priority >= SharedBackend.DEMAND_BOOST:
                self._run(r)
            else:
                self.pending.append(r)
        return len(batch)

    # IOPlane aliases submit_batch at class definition time; the subclass
    # must re-alias or SharedBackend views would bypass the override.
    submit_batch = submit

    def pump(self, k=None) -> int:
        """Run up to ``k`` queued requests (all of them when None) — the
        harness's stand-in for worker-pool progress."""
        n = 0
        while self.pending and (k is None or n < k):
            self._run(self.pending.pop(0))
            n += 1
        return n


class ManualView(SharedBackend):
    """A SharedBackend view safe to demand-wait on a single thread: a
    frontier request that is admitted but still queued on the manual plane
    runs inline instead of blocking on a worker that does not exist.
    (Deferred chains take the normal promotion path; evicted requests take
    the normal serve-as-demand recovery path.)"""

    def wait(self, req):
        with self._lock:
            deferred = any(req in chain for chain in self._deferred)
        if not deferred and not req.is_done() \
                and req.state is ReqState.PREPARED:
            self.inner._run(req)
        return super().wait(req)


def _make_files(dev, n=16, size=64):
    out = []
    for i in range(n):
        fd = dev.open(f"/o/f{i}", "w")
        payload = bytes([(i * 7 + 3) % 251]) * size
        dev.pwrite(fd, payload, 0)
        dev.close(fd)
        out.append((dev.open(f"/o/f{i}", "r"), size, 0, payload))
    return out


def _session_steps(idx, rng_seed, plane, sched, dev, graph, files, results):
    """Generator: one open-loop session, one intercept per step.  Created
    lazily — the view attaches (and the tenant appears in the scheduler)
    at the first step, exactly like an arrival."""
    rng = random.Random(rng_seed)
    k = rng.randrange(2, len(files) + 1)
    extents = rng.sample(files, k)
    stop_at = rng.randrange(len(extents))  # early exit: leftover speculation
    view = ManualView(plane, sched, tenant=f"s{idx}",
                      weight=1.0 + (idx % 3),
                      priority=("low", "normal", "high")[idx % 3])
    sess = SpecSession(graph, {"extents": [e[:3] for e in extents]},
                       view, dev, depth=4)
    try:
        for j, (fd, n, off, payload) in enumerate(extents):
            data = sess.intercept(Sys.PREAD, (fd, n, off))
            assert data == payload, f"session {idx} read corrupt bytes"
            if j == stop_at:
                break
            yield
    finally:
        stats = sess.finish()
        view.shutdown()
        results.append(stats)


def run_trace(sessions, rate, duration, capacity=12, seed=0,
              arrival_bias=0.85):
    """Replay one seeded arrival trace through the shared scheduler on a
    single thread and return the merged report.  ``arrival_bias`` is the
    probability a step admits the next arrival instead of advancing a live
    session — high bias piles sessions up, which is the point."""
    dev = MemDevice()
    files = _make_files(dev)
    plane = ManualPlane(dev)
    sched = SlotScheduler(capacity)
    graph = build_pread_extents_graph("openloop_scan", weak=True)
    schedule = arrival_schedule(sessions, rate, duration, seed=seed)
    assert schedule, "empty trace: nothing to test"
    clock = FakeClock()
    rng = random.Random(seed)
    results = []

    live = []  # (generator, arrival_s)
    events = []  # (arrival_s, completion_s) in virtual time
    peak_live = 0
    ai = 0
    while ai < len(schedule) or live:
        if ai < len(schedule) and (not live or rng.random() < arrival_bias):
            t_arr, idx = schedule[ai]
            ai += 1
            clock.advance_to(t_arr)
            g = _session_steps(idx, seed * 1000003 + idx, plane, sched,
                               dev, graph, files, results)
            try:
                next(g)  # first intercept: the session is now live
            except StopIteration:  # single-read session: done on arrival
                events.append((t_arr, clock.now()))
            else:
                live.append((g, t_arr))
                peak_live = max(peak_live, len(live))
        else:
            plane.pump(rng.randrange(0, 3))  # some worker progress
            i = rng.randrange(len(live))
            g, t_arr = live[i]
            try:
                next(g)
            except StopIteration:
                live.pop(i)
                events.append((t_arr, clock.now()))
    plane.pump()  # drain whatever speculation outlived its session

    total = SessionStats()
    for s in results:
        total.merge(s)
    return {
        "arrivals": len(schedule),
        "finished": len(results),
        "peak_live": peak_live,
        "max_inflight_virtual": max_inflight(events),
        "stats": total,
        "scheduler": sched.snapshot(),
        "plane": plane,
    }


def _check_invariants(rep):
    assert rep["finished"] == rep["arrivals"], "a session never finished"
    snap = rep["scheduler"]
    # fairness: demand never queues behind more speculation than capacity
    assert snap["max_spec_inflight"] <= snap["capacity"], snap
    # every admitted slot was released exactly once (completion callback)
    assert snap["spec_inflight"] == 0, snap
    # the deferred-reap path: no tenant state outlives its sessions
    assert snap["tenants"] == 0, snap
    s = rep["stats"]
    assert s.pre_issued == \
        s.served_async + s.cancelled + s.wasted_completions, vars(s)
    assert s.served_async > 0, "speculation never overlapped anything"
    assert snap["admitted"] > 0 and snap["evictions"] >= 0
    # the final pump drained the plane: nothing queued, nothing leaked
    assert not rep["plane"].pending
    assert rep["plane"].inflight() == 0


def test_scheduler_harness_small_trace_tier1():
    """Tier-1 size: ~128 arrivals, every invariant at drain."""
    rep = run_trace(sessions=128, rate=1.0, duration=1.0, capacity=12,
                    seed=3)
    assert rep["arrivals"] >= 64
    assert rep["peak_live"] >= 32, "interleaver never built concurrency"
    _check_invariants(rep)


def test_scheduler_harness_replays_identically():
    """The whole point of the fake clock + seeded interleaver: the same
    seed produces the same admissions, evictions, and stats — bit for
    bit."""
    a = run_trace(sessions=64, rate=1.0, duration=1.0, capacity=8, seed=9)
    b = run_trace(sessions=64, rate=1.0, duration=1.0, capacity=8, seed=9)
    assert a["scheduler"] == b["scheduler"]
    counts = ("intercepted", "pre_issued", "submits", "served_async",
              "served_sync", "cancelled", "wasted_completions")
    assert {f: getattr(a["stats"], f) for f in counts} == \
           {f: getattr(b["stats"], f) for f in counts}
    assert a["peak_live"] == b["peak_live"]
    assert a["max_inflight_virtual"] == b["max_inflight_virtual"]


def test_scheduler_harness_tiny_capacity_still_drains():
    """capacity=1 degenerates to demand-at-a-time with constant eviction
    pressure — the harshest admission/eviction interleaving."""
    rep = run_trace(sessions=48, rate=1.0, duration=1.0, capacity=1, seed=5)
    snap = rep["scheduler"]
    assert rep["finished"] == rep["arrivals"]
    assert snap["max_spec_inflight"] <= 1
    assert snap["spec_inflight"] == 0 and snap["tenants"] == 0
    s = rep["stats"]
    assert s.pre_issued == \
        s.served_async + s.cancelled + s.wasted_completions


@pytest.mark.stress
def test_scheduler_harness_1k_sessions():
    """The scale the O(1) admission path exists for: 1k+ concurrent tenant
    sessions on one shared backend, single-threaded, zero sleeps."""
    rep = run_trace(sessions=1024, rate=1.2, duration=1.0, capacity=24,
                    seed=7, arrival_bias=0.9)
    assert rep["arrivals"] >= 1000
    assert rep["peak_live"] >= 1000, \
        f"wanted 1k+ concurrent sessions, peaked at {rep['peak_live']}"
    _check_invariants(rep)
