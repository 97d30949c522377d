"""A configuration, a traffic mix and a per-layer metric are added with new
files and new entries alone, and a cell made of them runs through the
harness unchanged."""

import json
import os
import shutil

from bench.spec import CHECKOUT as REPO

DENSE = {
    "name": "tiny-dense",
    "source": "a dense decoder made up for this test",
    "reference": "decoder_lm",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 250,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "reduced": [],
    "assumed": {"head_dim": 16, "param_dtype": "bfloat16",
                "optimizer": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                              "weight_decay": 0.1, "grad_clip": 1.0,
                              "warmup_steps": 2, "total_steps": 1000,
                              "min_lr_ratio": 0.1, "keep_master": True}},
    "program_config": {"name": "tiny-dense", "vocab_size": 250, "d_model": 64,
                       "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
                       "head_dim": 16, "d_ff": 128, "tie_embeddings": True,
                       "attn_impl": "interpret", "loss_chunk": 32},
    "limits": {"grad1_gap": 0.2, "delta3_gap": 0.1, "grad1_diff": 0.5},
}
SHORT = {"kind": "train", "batch": 2, "seq_len": 32, "records": 32,
         "shards": 4, "warmup_steps": 3, "save_at_window_start": False}
READER = '''"""Steps the window completed."""


def read(ctx):
    return float(ctx.out["window_steps"]) or None
'''


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(os.path.join(REPO, "src"), root / "src")
    shutil.copytree(os.path.join(REPO, "tpubench"), root / "tpubench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    (root / "tpubench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(DENSE))
    (root / "tpubench" / "traffic" / "short.json").write_text(json.dumps(SHORT))
    (root / "tpubench" / "metrics" / "steps_seen.py").write_text(READER)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bj = json.load(f)
    bj["configs"].append({"name": "tiny-dense", "source": DENSE["source"],
                          "file": "tpubench/configs/tiny-dense.json",
                          "reduced": [], "why": "test"})
    bj["workloads"].append({"name": "tiny-dense.short", "config": "tiny-dense",
                            "traffic": "short", "chips": 1, "why": "test"})
    for m in bj["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-dense.short")
    bj["per_layer"].append({"name": "steps_seen", "unit": "steps",
                            "better": "higher", "source": "host_clock",
                            "layer": "test", "moves": "train_tokens_per_s",
                            "workloads": ["tiny-dense.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    return str(root)


def test_new_config_mix_and_metric_need_no_edit(bench, tmp_path):
    root = _checkout(tmp_path)
    args = ["--workload", "tiny-dense.short", "--seed", "9", "--seconds", "1",
            "--rehearse"]
    r = bench(args, checkout=root)
    assert r.rc == 0, r.stderr[-3000:]
    assert r.last["correct"] is True, r.last["checks"]
    assert set(r.last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    r = bench(args + ["--trace", "1"], checkout=root)
    assert r.rc == 0, r.stderr[-3000:]
    assert set(r.last["metrics"]) == {"steps_seen"}
    assert r.last["metrics"]["steps_seen"]["value"] >= 1
    assert r.last["device"]["window_s"] > 0
