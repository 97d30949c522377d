"""Per-arch smoke tests (reduced configs, real CPU step) + decode
consistency + MoE oracle equivalence + layer-group invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.models import mlp as mlpm
from repro.models.lm import layer_groups

RNG = jax.random.PRNGKey(0)
B, S = 2, 32


def make_batch(cfg, B, S, key=jax.random.PRNGKey(1)):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.visual_stub:
        batch["visual_embeds"] = jax.random.normal(key, (B, 8, cfg.d_model), jnp.float32)
    if cfg.enc_dec is not None:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_dec.n_audio_ctx, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward+backward on the reduced config: finite loss + grads,
    correct logits shape."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    batch = make_batch(cfg, B, S)
    loss = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: model.loss(p, batch))(params)
    gn = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    assert np.isfinite(float(gn)) and float(gn) > 0
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, S + 4))(params, batch)
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits[:, : cfg.vocab_size])).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_matches_forward(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(RNG)
    batch = make_batch(cfg, B, S)
    toks = batch["tokens"]
    P = S - 4
    pb = dict(batch)
    pb["tokens"] = toks[:, :P]
    logits, cache = model.prefill(params, pb, S)
    if model.is_enc_dec:
        for t in range(P, S):
            logits, cache = model.decode_step(
                params, cache, toks[:, t], jnp.full((B,), t, jnp.int32))
        full_logits, _ = model.prefill(params, batch, S)
        np.testing.assert_allclose(
            np.asarray(logits)[:, : cfg.vocab_size],
            np.asarray(full_logits)[:, : cfg.vocab_size], atol=2e-3, rtol=2e-3)
        return
    full = model.logits(params, batch)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, P - 1]),
                               atol=2e-3, rtol=2e-3)
    for t in range(P, S):
        logits, cache = model.decode_step(
            params, cache, toks[:, t], jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, t]),
                                   atol=2e-3, rtol=2e-3)


def test_moe_capacity_and_dropless_match_oracle():
    cfg = get_config("granite_moe_3b_a800m", smoke=True)
    cfg = replace(cfg, moe=replace(cfg.moe, dropless=False, capacity_factor=8.0,
                                   group_tokens=32))
    k = jax.random.PRNGKey(3)
    p = mlpm.moe_init(cfg, k)
    x = jax.random.normal(k, (2, 64, cfg.d_model), jnp.float32)
    y_cap, stats = mlpm.moe_apply(cfg, p, x)
    y_oracle = mlpm.moe_apply_dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_oracle),
                               atol=2e-4, rtol=2e-4)
    cfg2 = replace(cfg, moe=replace(cfg.moe, dropless=True))
    y_dl, _ = mlpm.moe_apply(cfg2, p, x)
    np.testing.assert_allclose(np.asarray(y_dl), np.asarray(y_oracle),
                               atol=2e-4, rtol=2e-4)
    assert float(stats["aux"]) >= 0


def test_moe_capacity_drops_bounded():
    """With cf=1 some tokens may drop, but output stays finite and close in
    norm to the oracle (regularization-level deviation, not corruption)."""
    cfg = get_config("granite_moe_3b_a800m", smoke=True)
    cfg = replace(cfg, moe=replace(cfg.moe, dropless=False, capacity_factor=1.0,
                                   group_tokens=64))
    k = jax.random.PRNGKey(4)
    p = mlpm.moe_init(cfg, k)
    x = jax.random.normal(k, (2, 64, cfg.d_model), jnp.float32)
    y, _ = mlpm.moe_apply(cfg, p, x)
    y_oracle = mlpm.moe_apply_dense_oracle(cfg, p, x)
    assert np.isfinite(np.asarray(y)).all()
    rel = float(jnp.linalg.norm(y - y_oracle) / jnp.linalg.norm(y_oracle))
    assert rel < 0.9


def test_layer_groups_partition_blocks():
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        if cfg.enc_dec is not None:
            continue
        gs = layer_groups(cfg)
        assert sum(g.count for g in gs) == cfg.n_layers
        # groups tile the pattern contiguously
        i = 0
        for g in gs:
            assert g.start == i
            for j in range(g.count):
                assert cfg.blocks[i + j] == g.kind
            i += g.count


def test_mrope_equals_rope_for_text_positions():
    """With all three position streams equal, M-RoPE must reduce to RoPE."""
    from repro.models.common import apply_mrope, apply_rope

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (2, 16, 4, 32), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 16))
    a = apply_rope(x, pos, 10000.0)
    b = apply_mrope(x, pos3, 10000.0, (4, 6, 6))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def _check_chunked_loss(*, head="emb", chunk=64, pad=0, softcap=0.0,
                        mask="none", dtype=jnp.float32, w_scale=0.02):
    """Loss and gradients (h, embedding, output head) of the chunked loss,
    jitted under ``value_and_grad(has_aux=True)`` as the train step calls
    it, against autodiff of the full-softmax ``lm_head_logits`` loss.

    ``head``: "emb" (no output weight: the embedding is the head), "tied"
    (``tie_embeddings``, an output weight given and ignored), "untied"."""
    from repro.models.common import chunked_softmax_xent, lm_head_logits

    base = get_config("tinyllama_1_1b", smoke=True)
    cfg = replace(base, loss_chunk=chunk, logit_softcap=softcap,
                  vocab_size=base.padded_vocab - pad,
                  tie_embeddings=head == "tied")
    assert cfg.padded_vocab == base.padded_vocab
    Bt, St, V, D = 2, 64, cfg.padded_vocab, cfg.d_model
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    emb = {"tok": (jax.random.normal(k[0], (V, D)) * w_scale).astype(dtype)}
    out_w = (None if head == "emb"
             else (jax.random.normal(k[1], (V, D)) * w_scale).astype(dtype))
    h = jax.random.normal(k[2], (Bt, St, D), jnp.float32).astype(dtype)
    labels = jax.random.randint(k[3], (Bt, St), 0, cfg.vocab_size)
    m = {"none": None,
         "zeros": (jax.random.uniform(k[4], (Bt, St)) > 0.3).astype(jnp.float32),
         "all_zero": jnp.zeros((Bt, St), jnp.float32)}[mask]

    def chunked(h, emb, out_w):
        loss = chunked_softmax_xent(cfg, emb, out_w, h, labels, m)
        return 2.0 * loss, loss

    def full(h, emb, out_w):
        logits = lm_head_logits(cfg, emb, out_w, h)
        lse = jax.nn.logsumexp(logits, -1)
        lab = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        mm = jnp.ones((Bt, St), jnp.float32) if m is None else m
        loss = ((lse - lab) * mm).sum() / jnp.maximum(mm.sum(), 1.0)
        return 2.0 * loss, loss

    def run(f):
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(
            h, emb, out_w)

    (v1, l1), g1 = run(chunked)
    (v2, l2), g2 = run(full)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5, atol=1e-6)
    if mask == "all_zero":
        assert float(l1) == 0.0
    leaves1, leaves2 = jax.tree.leaves(g1), jax.tree.leaves(g2)
    assert len(leaves1) == len(leaves2) == (2 if head == "emb" else 3)
    for a, b in zip(leaves1, leaves2):
        assert a.dtype == b.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(float(np.abs(b).max()), 1e-30)
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * scale)
    if head == "tied":
        assert not np.asarray(g1[2], np.float32).any()  # the head is the embedding


def test_chunked_loss_matches_full_softmax():
    _check_chunked_loss()


@pytest.mark.parametrize("case", [
    dict(head="untied", chunk=16),
    dict(head="tied", chunk=16, dtype=jnp.bfloat16),
    dict(pad=7, chunk=16, mask="zeros"),
    dict(softcap=30.0, chunk=8, w_scale=0.5),
    dict(softcap=30.0, pad=7, chunk=16, head="untied", mask="zeros",
         dtype=jnp.bfloat16, w_scale=0.5),
    dict(mask="all_zero", chunk=16),
    dict(mask="zeros", chunk=64, dtype=jnp.bfloat16),
], ids=["untied-4chunks", "tied-bf16", "padded-vocab-masked",
        "softcap-8chunks", "softcap-padded-untied-bf16", "all-zero-mask",
        "masked-1chunk-bf16"])
def test_chunked_loss_and_grads_match_full_softmax(case):
    _check_chunked_loss(**case)


def test_chunked_loss_grad_keeps_no_stacked_logits():
    """The differentiated loss keeps no chunk's (B,C,V) float32 logits for
    the backward: its compiled temporaries stay below one stacked-logits
    buffer (nchunks x B x C x V x 4 bytes), which autodiff through the
    chunk scan would write."""
    from repro.models.common import chunked_softmax_xent

    Bt, St, C = 2, 1024, 64
    cfg = replace(get_config("tinyllama_1_1b", smoke=True), loss_chunk=C,
                  vocab_size=4096)
    V, D = cfg.padded_vocab, cfg.d_model

    def loss(h, w, labels):
        return chunked_softmax_xent(cfg, {"tok": w}, None, h, labels)

    args = (jax.ShapeDtypeStruct((Bt, St, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((V, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((Bt, St), jnp.int32))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    stacked = (St // C) * Bt * C * V * 4
    assert compiled.memory_analysis().temp_size_in_bytes < stacked


def test_yarn_frequencies_and_scale_as_published():
    """YaRN (DeepSeek-V2's rope_scaling) against a plain transcription of
    the published formulas: correction range, linear ramp, mscale."""
    import math

    from repro.configs.deepseek_v2_lite import YARN
    from repro.models.common import rope_freqs, rope_mscale
    from repro.models.config import yarn_mscale

    dim, base = 64, 10000.0
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, base, YARN)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, base)), extra,
                               rtol=1e-6)
    # mscale == mscale_all_dim: cos and sin unscaled; the softmax scale
    # takes mscale(40, 0.707)^2 = 1.5897
    assert rope_mscale(YARN) == 1.0
    assert yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.58966, rel=1e-4)


def test_model_config_takes_nested_groups_as_mappings():
    from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                     YarnScaling)

    cfg = ModelConfig(
        name="m", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, d_ff=128, block_pattern=["mla", "mla"],
        mla={"q_lora": 0, "kv_lora": 16, "qk_nope": 8, "qk_rope": 8,
             "v_head": 8},
        moe={"num_experts": 8, "top_k": 2, "d_expert": 16,
             "experts_held": 2, "expert_offset": 6},
        rope_scaling={"type": "yarn", "factor": 40,
                      "original_max_position_embeddings": 4096})
    assert cfg.block_pattern == ("mla", "mla")
    assert isinstance(cfg.mla, MLAConfig) and cfg.mla.q_lora == 0
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.held == 2
    assert isinstance(cfg.rope_scaling, YarnScaling)
    assert cfg == replace(cfg)          # frozen, hashable groups
    with pytest.raises(ValueError):
        MoEConfig(num_experts=8, top_k=2, d_expert=16, experts_held=4,
                  expert_offset=6)
    with pytest.raises(ValueError):
        ModelConfig(name="m", vocab_size=256, d_model=64, n_layers=1,
                    n_heads=4, n_kv_heads=4, d_ff=128,
                    rope_scaling={"type": "linear", "factor": 2,
                                  "original_max_position_embeddings": 4096})


@pytest.mark.parametrize("norm_topk,routed_scale", [(False, 1.0), (False, 16.0),
                                                    (True, 2.5)])
def test_moe_gates_raw_or_renormalised_and_scaled(norm_topk, routed_scale):
    """Capacity and dropless paths against the dense oracle with DeepSeek's
    gate options, and the counts of a layer that drops nothing."""
    cfg = get_config("granite_moe_3b_a800m", smoke=True)
    moe = replace(cfg.moe, dropless=False, capacity_factor=8.0, group_tokens=32,
                  norm_topk=norm_topk, routed_scale=routed_scale)
    cfg = replace(cfg, moe=moe)
    k = jax.random.PRNGKey(5)
    p = mlpm.moe_init(cfg, k)
    x = jax.random.normal(k, (2, 64, cfg.d_model), jnp.float32)
    y_cap, stats = mlpm.moe_apply(cfg, p, x)
    y_oracle = mlpm.moe_apply_dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_oracle),
                               atol=2e-4 * routed_scale, rtol=2e-4)
    assert int(stats["moe_assigned"]) == int(stats["moe_kept"]) == 2 * 64 * 2
    cfg2 = replace(cfg, moe=replace(moe, dropless=True))
    y_dl, _ = mlpm.moe_apply(cfg2, p, x)
    np.testing.assert_allclose(np.asarray(y_dl), np.asarray(y_oracle),
                               atol=2e-4 * routed_scale, rtol=2e-4)


def test_moe_share_of_held_experts():
    """A chip holding experts 2 and 3 of 8: the capacity path agrees with
    the oracle's share and counts only those experts' picks; the dropless
    path holds every expert."""
    cfg = get_config("granite_moe_3b_a800m", smoke=True)
    full = replace(cfg.moe, dropless=False, capacity_factor=8.0,
                   group_tokens=32)
    k = jax.random.PRNGKey(6)
    p = mlpm.moe_init(replace(cfg, moe=full), k)
    x = jax.random.normal(k, (2, 64, cfg.d_model), jnp.float32)
    share = replace(full, experts_held=2, expert_offset=2)
    ps = dict(p, **{n: p[n][2:4] for n in ("wi", "wg", "wo")})
    c = replace(cfg, moe=share)
    y, stats = mlpm.moe_apply(c, ps, x)
    y_oracle = mlpm.moe_apply_dense_oracle(c, ps, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_oracle),
                               atol=2e-4, rtol=2e-4)
    ids = jax.lax.top_k(jax.nn.softmax(
        x.reshape(-1, cfg.d_model) @ p["router"], -1), 2)[1]
    want = int(((ids >= 2) & (ids < 4)).sum())
    assert int(stats["moe_assigned"]) == int(stats["moe_kept"]) == want > 0
    with pytest.raises(NotImplementedError):
        mlpm.moe_apply(replace(c, moe=replace(share, dropless=True)), ps, x)
