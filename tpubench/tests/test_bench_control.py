"""The controls come out as not correct: the reference computed in fp8 in
the program's place (training), and a restore that rounds the
float32 leaves to bfloat16 (resume), at the rehearsal size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from bench import data as bdata
from bench.compare import train_gaps
from bench.digest import tree_digest
from bench.spec import load_cell
from reference import load_reference


def _cell(name):
    return bench_run.rehearsal(load_cell(name))


@pytest.mark.parametrize("seed", [101, 102, 103])
def test_fp8_control_fails_the_training_limits(seed):
    cell = _cell("granite-moe-3b-a800m.train")
    cfg, t = cell.config, cell.traffic
    ref = load_reference(cfg)
    tokens = bdata.make_tokens(seed, t["records"], t["seq_len"] + 1,
                               cfg["vocab_size"])
    first = []
    for s in range(3):
        b = bdata.expected_batch(tokens, seed, t["batch"], 0, s)
        first.append((b[:, :-1], b[:, 1:]))
    key = jax.random.PRNGKey(seed)
    f32 = ref.train_readings(cfg, key, first, keep_grad1=True)
    fp8 = ref.train_readings(cfg, key, first, quant="fp8",
                             grad1_of=f32["grad1_leaves"])
    gaps = train_gaps(fp8, f32, fp8["grad1_diff"])
    assert any(gaps[k] > lim for k, lim in cfg["limits"].items()), gaps
    # fp8 rounds the gradients and does not wipe them out
    assert np.all(fp8["grad1"] > 0), fp8["grad1"]


def test_lower_precision_restore_fails_the_resume_check():
    cell = _cell("granite-moe-3b-a800m.resume")
    ref = load_reference(cell.config)
    state = jax.jit(lambda k: ref.train_state(cell.config, k))(
        jax.random.PRNGKey(5))
    want = np.asarray(tree_digest(state))
    lowered = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if x.dtype == jnp.float32 else x, state)
    got = np.asarray(tree_digest(lowered))
    assert np.array_equal(np.asarray(tree_digest(state)), want)
    assert np.any(got != want, axis=1).sum() > 0
