"""Each traffic mix, end to end through the harness on the CPU at its
rehearsal size: a contract-shaped last line with ``correct`` true."""

import os
import re
import shutil

import pytest

from bench import flops
from bench.spec import CHECKOUT as REPO
from bench.spec import load_cell

CELLS = ["granite-moe-3b-a800m.train", "granite-moe-3b-a800m.train_save",
         "granite-moe-3b-a800m.resume"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_a_correct_result(bench, cell):
    r = bench(["--workload", cell, "--seed", "2147483659", "--seconds", "1",
               "--rehearse"])
    assert r.rc == 0, r.stderr[-3000:]
    out = r.last
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["rehearsal"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m.name for m in load_cell(cell).end_to_end}
    assert set(out["metrics"]) == want and "setup_s" in want
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    # the numbers compared close standard error, each beside its limit
    tail = r.stderr.strip().splitlines()[-len(out["checks"]):]
    assert all(ln.startswith("check ") and " limit " in ln for ln in tail)
    # the train-state bytes the run holds are the file's count
    m = re.search(r"train_state_bytes (\d+) \(configuration file: (\d+)\)",
                  r.stdout)
    assert m and m.group(1) == m.group(2)


def test_measured_run_without_a_tpu_fails(bench):
    r = bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert r.rc != 0
    assert r.last is None and "{" not in r.stdout


def test_benchmark_alone_without_the_program_fails(bench, tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), alone)
    shutil.copytree(os.path.join(REPO, "tpubench"), alone / "tpubench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    r = bench(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--rehearse"], checkout=str(alone))
    assert r.rc != 0 and r.last is None


def test_state_bytes_of_the_cell_config():
    cfg = load_cell(CELLS[0]).config
    # 2 layers x (attention 6,291,456 + router 61,440 + experts 94,371,840
    # + norms 3,072) + tied embedding 49,408 x 1,536 + final norm 1,536
    assert flops.param_count(cfg) == 2 * (6291456 + 61440 + 94371840 + 3072) \
        + 49408 * 1536 + 1536
    # 14 bytes a parameter, 16 for the float32 router, + the int32 step
    assert flops.train_state_bytes(cfg) == 14 * flops.param_count(cfg) \
        + 2 * 2 * 61440 + 4
