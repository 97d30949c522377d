"""Operation counts against hand counts, the digests on both sides, and
the trace reduction on small traces."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.digest import host_digests, leaf_digest_np, tree_digest
from bench.spec import load_cell
from bench.trace import from_events

GRANITE = load_cell("granite-moe-3b-a800m.train").config
DENSE = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 3,
         "intermediate_size": 256, "vocab_size": 1000}


def test_granite_flops_by_hand():
    # per layer: q 1536x1536, k and v 1536x512 each, o 1536x1536
    # = 6,291,456; router 1536x40 = 61,440; 8 experts x 3 x 1536 x 512
    # = 18,874,368; two layers; head 1536 x 49,155 = 75,502,080
    assert flops.active_params_per_token(GRANITE) == 2 * (6291456 + 61440
                                                          + 18874368) + 75502080
    # 6 N + 6 x 2 layers x 24 heads x 64 x 2048
    assert flops.train_flops_per_token(GRANITE, 2048) == \
        6 * 125956608 + 6 * 2 * 24 * 64 * 2048
    fl, nb = flops.flash_fwd_cost(GRANITE, 8, 2048)
    assert fl == 2 * 8 * 24 * 2048 * 2048 * 64 == 103079215104
    # q and o of 24 heads, k and v of 8, bf16
    assert nb == 2 * (2 * 8 * 24 * 2048 * 64 + 2 * 8 * 8 * 2048 * 64)


def test_dense_flops_by_hand():
    # head_dim 32: q 128x128, k and v 128x64, o 128x128; SwiGLU 3x128x256
    assert flops.active_params_per_token(DENSE) == 3 * (49152 + 98304) + 128000
    assert flops.train_flops_per_token(DENSE, 512) == \
        6 * 570368 + 6 * 3 * 4 * 32 * 512


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int32])
def test_digest_agrees_on_both_sides_and_sees_one_bit(dtype):
    x = (np.arange(1000) * 7 % 113).astype(np.float32).reshape(10, 100)
    a = jnp.asarray(x, dtype)
    dev = np.asarray(tree_digest({"a": a}))
    host = host_digests([np.asarray(a)])
    assert np.array_equal(dev, host)
    b = np.asarray(a).copy()
    b.reshape(-1).view(np.uint8)[3] ^= 1
    assert not np.array_equal(leaf_digest_np(b), host[0])
    c = np.asarray(a).copy().reshape(-1)
    c[[1, 2]] = c[[2, 1]]
    assert not np.array_equal(leaf_digest_np(c.reshape(10, 100)), host[0])


def test_trace_reduction_idle_share_kernel_time_and_gaps():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("_fwd_kernel", 2.0, 2.0),
                             ("fusion.2", 6.0, 1.0), ("early", -3.0, 1.0)]}
    spans = [("window", 0.0, 10.0), ("step", 0.5, 5.5), ("input", 5.5, 6.0)]
    tr = from_events(dev, spans)
    assert tr.window_s == 10.0
    assert tr.busy_s() == pytest.approx(4.0)          # [1, 4) and [6, 7)
    assert tr.op_time(lambda n: "kernel" in n) == (2.0, 1)
    assert tr.idle_gaps(3) == [["outside spans", 3.0], ["step", 2.0], ["step", 1.0]]
    assert tr.top_ops(2) == [["fusion.1", 2.0], ["_fwd_kernel", 2.0]]


def test_a_qualified_metric_reads_its_quantity():
    import run as bench_run
    from bench.spec import BENCH_DIR, quantity

    cell = load_cell("granite-moe-3b-a800m.train_save")
    names = {m.name for m in cell.end_to_end + cell.per_layer}
    assert {"train_tokens_per_s.save", "step_mfu.save"} <= names
    assert "train_tokens_per_s" not in names and "step_mfu" not in names
    assert quantity("step_mfu.save") == "step_mfu"

    def source(name):
        return bench_run.load_metric_reader(BENCH_DIR, name).__code__.co_filename

    assert source("step_mfu.save") == source("step_mfu")
    assert source("async_share.resume").endswith("async_share.resume.py")


def _recorded():
    """0.45 s of a traced granite-moe-3b-a800m.train window on one v5e:
    device operations (name, start, duration; seconds from the slice's
    start) and the harness spans open in it."""
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "train_trace_slice.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    spans = [tuple(s) for s in rec["spans"] if s[0] != "window"]
    spans.append(("window", *rec["window"]))
    return from_events({"/device:TPU:0": [tuple(e) for e in rec["device"]]},
                       spans)


def _covered(intervals, lo, hi):
    """Seconds of [lo, hi) covered by any interval: a sweep over sorted
    end points, counting how many intervals are open."""
    points = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort()
    open_, last, total = 0, lo, 0.0
    for t, d in points:
        if open_ > 0:
            total += t - last
        open_ += d
        last = t
    return total


def test_trace_reduction_on_a_recorded_trace():
    from bench.peaks import peaks

    tr = _recorded()
    lo, hi = tr.window
    want_busy = _covered([(o.start, o.start + o.dur) for o in tr.ops], lo, hi)
    assert tr.busy_s() == pytest.approx(want_busy, rel=1e-9)
    assert 0.9 < tr.busy_s() / tr.window_s < 1.0
    # the flash-attention forward: five calls in the slice, each over a
    # whole layer's heads at batch 8 x 2048
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "flash_reader", os.path.join(os.path.dirname(os.path.dirname(__file__)),
                                     "metrics", "flash_attn_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    match = reader.kernel_matcher(GRANITE, 8, 2048)
    calls = [o for o in tr.ops if match(o.name) and lo <= o.start < hi]
    seconds, count = tr.op_time(match)
    assert count == len(calls) == 5
    assert seconds == pytest.approx(sum(o.dur for o in calls))
    fl, nb = flops.flash_fwd_cost(GRANITE, 8, 2048)
    pk = peaks("TPU v5 lite")
    share = max(fl / pk["bf16_flops"], nb / pk["hbm_bytes_per_s"]) * count / seconds
    assert 0.005 < share < 0.05          # the kernel runs far from its roofline
    assert tr.idle_gaps(1)[0][0] in {"input", "put", "step", "outside spans"}
