"""DeepSeek-V2-Lite against its plain reference on the CPU at small sizes:
latent attention with YaRN, one train step's loss and gradients, the
expert-parallel share of an MoE layer, the fp8 control, the operation
counts, the readers of the cell's per-layer metrics, and the cell's
rehearsal end to end."""

import copy
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from bench import data as bdata
from bench import drive, flops_mla
from bench import program_spans as ps
from bench.compare import train_gaps
from bench.spec import BENCH_DIR, load_cell
from bench.trace import from_events
from reference import deepseek_v2 as ref

CELL = "deepseek-v2-lite.train_8k"


def _rehearsal_cfg(**assumed):
    cfg = copy.deepcopy(bench_run.rehearsal(load_cell(CELL)).config)
    cfg["assumed"].update(assumed)
    return cfg


def _program_cfg(cfg, **over):
    """The program's ModelConfig from the file's ``program_config`` mapping
    (nested groups as mappings, as the harness hands them over)."""
    from repro.models.config import ModelConfig

    pc = dict(copy.deepcopy(cfg["program_config"]), **over)
    return ModelConfig(**pc)


def _f32(cfg):
    """A float32 configuration: reference weights and program alike."""
    cfg = copy.deepcopy(cfg)
    cfg["assumed"]["param_dtype"] = "float32"
    return cfg, _program_cfg(cfg, param_dtype="float32",
                             compute_dtype="float32", attn_impl="ref")


def test_program_config_builds_from_the_file():
    from repro.models import build_model
    from repro.models.config import MLAConfig, MoEConfig, YarnScaling

    cell = load_cell(CELL)
    pcfg = drive.model_config(cell)
    assert isinstance(pcfg.mla, MLAConfig) and isinstance(pcfg.moe, MoEConfig)
    assert isinstance(pcfg.rope_scaling, YarnScaling)
    assert pcfg.block_pattern == ("mla",) * 5
    # every published width
    assert (pcfg.d_model, pcfg.n_heads, pcfg.d_ff) == (2048, 16, 10944)
    assert (pcfg.mla.q_lora, pcfg.mla.kv_lora, pcfg.mla.qk_nope,
            pcfg.mla.qk_rope, pcfg.mla.v_head) == (0, 512, 128, 64, 128)
    m = pcfg.moe
    assert (m.num_experts, m.top_k, m.d_expert, m.num_shared, m.dense_d_ff) \
        == (64, 6, 1408, 2, 10944)
    assert (m.held, m.expert_offset, m.norm_topk) == (8, 0, False)
    model = build_model(pcfg)
    key = jax.random.PRNGKey(0)
    prog = jax.eval_shape(model.init, key)
    want = jax.eval_shape(lambda k: ref.init_params(cell.config, k), key)
    assert jax.tree.structure(prog) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(prog)] == \
        [x.shape for x in jax.tree.leaves(want)]
    n = sum(x.size for x in jax.tree.leaves(prog))
    assert n == flops_mla.param_count(cell.config) == 535_060_992


def test_flops_mla_by_hand():
    cfg = load_cell(CELL).config
    # MLA: wq 2048x16x192 6,291,456; kv_down 2048x576 1,179,648; k_up and
    # v_up 512x16x(128+128) 2,097,152; wo 16x128x2048 4,194,304
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    dense = attn + 3 * 2048 * 10944
    # router 2048x64; 6 x 8/64 = 0.75 held experts of 3x2048x1408; shared
    # 3x2048x2816
    moe = attn + 131_072 + 0.75 * 8_650_752 + 17_301_504
    n = dense + 4 * moe + 2048 * 12800
    assert flops_mla.active_params_per_token(cfg) == n == 257_949_696
    # 6 N + 3 x 5 layers x 16 heads x 8192 x (192 + 128)
    assert flops_mla.train_flops_per_token(cfg, 8192) == \
        6 * n + 3 * 5 * 16 * 8192 * 320
    fl, nb = flops_mla.mla_fwd_cost(cfg, 2, 8192)
    assert fl == 2 * 16 * 8192 * 8192 * 320
    # q and k at 192, v and o at 128, bf16
    assert nb == 2 * (2 * 16 * 8192) * (192 + 192 + 128 + 128)
    # 14 bytes a parameter, 16 for the float32 router, + the int32 step
    assert flops_mla.train_state_bytes(cfg) == \
        14 * 535_060_992 + 2 * 4 * 131_072 + 4 == 7_491_902_468


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_mla_forward_matches_the_reference(impl):
    from repro.models import attention

    cfg, pcfg = _f32(_rehearsal_cfg())
    pcfg = _program_cfg(cfg, param_dtype="float32", compute_dtype="float32",
                        attn_impl=impl)
    assert pcfg.mla.q_lora == 0 and pcfg.rope_scaling is not None
    d = ref.Dims(cfg)
    p = ref.init_params(cfg, jax.random.PRNGKey(7))
    pa = jax.tree.map(lambda x: x[0], p["layers"][1]["attn"])
    B, S = 2, 64
    h = jax.random.normal(jax.random.PRNGKey(8), (B, S, d.D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    got = attention.mla_apply(pcfg, pa, h, pos)
    want = jnp.stack([ref._attention_row(d, pa, h[b], None, block=16)
                      for b in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_train_step_loss_and_first_gradient_match_the_reference():
    from repro.models import build_model

    cfg, pcfg = _f32(_rehearsal_cfg())
    model = build_model(pcfg)
    p = ref.init_params(cfg, jax.random.PRNGKey(11))
    tok = jax.random.randint(jax.random.PRNGKey(12), (2, 64), 0,
                             cfg["vocab_size"])
    lab = jnp.roll(tok, -1, axis=1)
    lp, gp = jax.jit(jax.value_and_grad(model.loss))(
        p, {"tokens": tok, "labels": lab})
    lr, gr = jax.jit(jax.value_and_grad(
        lambda q: ref.loss(cfg, q, tok, lab)))(p)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.linalg.norm(b)) + 1e-12
        assert float(jnp.linalg.norm(a - b)) / scale < 1e-3


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 experts held 2 at a time, with capacity to drop nothing: the
    routed parts of the four shares, with the shared experts counted
    once, are the reference's layer with all 8 experts held."""
    from repro.models import mlp

    cfg = _rehearsal_cfg(capacity_factor=8.0, expert_offset=0,
                         param_dtype="float32")
    cfg["n_routed_experts"] = cfg["published"]["n_routed_experts"] = 8
    cfg["routed_scaling_factor"] = 2.5
    d = ref.Dims(cfg)
    p = ref.init_params(cfg, jax.random.PRNGKey(21))
    pf = jax.tree.map(lambda x: x[0], p["layers"][1]["ffn"])
    B, S = 2, 64
    h = jax.random.normal(jax.random.PRNGKey(22), (B, S, d.D), jnp.float32)
    want, _ = ref._moe(d, pf, h, None)

    total = jnp.zeros_like(h)
    assigned = kept = 0
    for off in range(0, 8, 2):
        pcfg = _program_cfg(cfg, param_dtype="float32",
                            compute_dtype="float32",
                            moe=dict(cfg["program_config"]["moe"],
                                     num_experts=8, capacity_factor=8.0,
                                     experts_held=2, expert_offset=off,
                                     routed_scale=2.5))
        share = dict(pf, **{k: pf[k][off:off + 2] for k in ("wi", "wg", "wo")})
        y, stats = mlp.moe_apply(pcfg, share, h)
        total = total + y - mlp._shared(pcfg, pf["shared"], h)
        assigned += int(stats["moe_assigned"])
        kept += int(stats["moe_kept"])
    total = total + mlp._shared(pcfg, pf["shared"], h)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert assigned == kept == B * S * d.K


@pytest.mark.parametrize("seed", [101, 102, 103])
def test_fp8_control_fails_the_training_limits(seed):
    cfg = _rehearsal_cfg()
    t = bench_run.rehearsal(load_cell(CELL)).traffic
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
    assert np.all(fp8["grad1"] > 0), fp8["grad1"]


def test_rehearsal_of_the_8k_cell_ends_correct(bench):
    r = bench(["--workload", CELL, "--seed", "3000000019", "--seconds", "1",
               "--rehearse"], timeout=600)
    assert r.rc == 0, r.stderr[-3000:]
    out = r.last
    assert out["correct"] is True and out["rehearsal"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # the state the run holds is the latent-attention count's, not the
    # dense count run.py prints beside it
    m = re.search(r"train_state_bytes (\d+) \(configuration file", r.stdout)
    assert m and int(m.group(1)) == flops_mla.train_state_bytes(
        bench_run.rehearsal(load_cell(CELL)).config)


# -- readers of the cell's per-layer metrics -----------------------------------
def _reader(name):
    return bench_run.load_metric_reader(BENCH_DIR, name)


def test_expert_kept_share_reads_the_compute_spans(monkeypatch):
    T = "/host:CPU/0"
    events = [("trainer.compute", 1, 2, T, {"step": 3, "moe_assigned": 1000,
                                            "moe_kept": 950}),
              ("trainer.compute", 3, 4, T, {"step": 4, "moe_assigned": 1000,
                                            "moe_kept": 1000}),
              ("trainer.compute", 20, 21, T, {"step": 9, "moe_assigned": 10,
                                              "moe_kept": 0})]
    monkeypatch.setattr(ps, "read_events", lambda trace_dir: events)
    tr = from_events({"/device:TPU:0": []}, [("window", 0.0, 10.0)])
    ctx = SimpleNamespace(trace_dir="trace", trace_data=tr, out={})
    assert _reader("expert_kept_share")(ctx) == pytest.approx(97.5)
    # a program that attaches no counts reads nothing
    events[:] = [(n, a, b, t, {"step": 1}) for n, a, b, t, _ in events]
    assert _reader("expert_kept_share")(ctx) is None


def test_mla_attn_roofline_matches_the_forward_only(monkeypatch):
    from bench import peaks

    q, v = "bf16[2,16,8192,192]", "bf16[2,16,8192,128]"
    fwd = f"tpu_custom_call({q} p0, {q} p1, {v} p2) -> {v}"
    ops = [(fwd, 0.0, 0.007), (fwd, 1.0, 0.007),
           ("fusion(bf16[2,16,8192,64] a)", 2.0, 0.5)]
    tr = from_events({"/device:TPU:0": ops}, [("window", 0.0, 10.0)])
    monkeypatch.setattr(peaks, "PEAKS", {"cpu": peaks.PEAKS["TPU v5e"]})
    ctx = SimpleNamespace(trace_data=tr, chips=1, cell=load_cell(CELL))
    # the least time of a call is 6.87e11 / 197e12 s (compute bound)
    want = 100.0 * (2 * 16 * 8192 * 8192 * 320 / 197e12) / 0.007
    assert _reader("mla_attn_roofline")(ctx) == pytest.approx(want, rel=1e-6)
    assert _reader("mla_attn_roofline")(SimpleNamespace(
        trace_data=from_events({"/device:TPU:0": ops[2:]},
                               [("window", 0.0, 10.0)]),
        chips=1, cell=load_cell(CELL))) is None


def test_step_mfu_of_the_mla_cell(monkeypatch):
    from bench import peaks

    monkeypatch.setattr(peaks, "PEAKS", {"cpu": peaks.PEAKS["TPU v5e"]})
    cell = load_cell(CELL)
    ctx = SimpleNamespace(out={"window_steps": 50, "tokens_per_step": 16384},
                          chips=1, cell=cell, window=(0.0, 50.0))
    per_token = flops_mla.train_flops_per_token(cell.config, 8192)
    assert _reader("step_mfu.mla")(ctx) == pytest.approx(
        100.0 * 16384 * per_token / 197e12)
