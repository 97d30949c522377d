"""The harness driven with the timed path broken underneath: each fault
the cells can have makes ``correct`` come out false."""

import pytest

FAULTS = [
    ("granite-moe-3b-a800m.train", "state_unchanged",
     ("grad1_gap", "delta3_gap", "grad1_diff")),
    ("granite-moe-3b-a800m.train", "half_batch", ("grad1_gap", "grad1_diff")),
    ("granite-moe-3b-a800m.train", "token_altered", ("batches_wrong",)),
    ("granite-moe-3b-a800m.train_save", "ckpt_altered", ("ckpt_leaves_wrong",)),
    ("granite-moe-3b-a800m.resume", "restore_altered", ("resumes_wrong",)),
]


@pytest.mark.parametrize("cell,fault,caught_by", FAULTS,
                         ids=[f for _, f, _ in FAULTS])
def test_fault_makes_the_run_incorrect(bench, cell, fault, caught_by):
    r = bench(["--workload", cell, "--seed", "3", "--seconds", "1",
               "--rehearse"], fault=fault)
    assert r.rc == 0, r.stderr[-3000:]
    assert r.last["correct"] is False
    failed = {k for k, c in r.last["checks"].items() if c["value"] > c["limit"]}
    assert set(caught_by) <= failed
