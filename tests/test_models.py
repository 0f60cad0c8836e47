"""Assigned-architecture substrate tests: per-arch smoke (reduced
configs, one forward/train step, shape + finiteness), decode-vs-forward
consistency, ring-buffer local attention, recurrent state semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm as LM
from repro.models import registry as R


ARCH_IDS = list(R.ARCHS)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """REQUIRED per assignment: reduced config, one train step on CPU,
    output shapes + no NaNs."""
    cfg = R.get_config(arch, smoke=True)
    init_state, step = R.make_train_step(cfg, remat=False)
    state = init_state(jax.random.key(0))
    if R.is_encdec(cfg):
        batch = {"frames": jnp.ones((2, 16, cfg.d_model), jnp.bfloat16),
                 "tokens": jnp.zeros((2, 8), jnp.int32),
                 "labels": jnp.ones((2, 8), jnp.int32)}
    else:
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
    state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_shapes(arch):
    cfg = R.get_config(arch, smoke=True)
    if R.is_encdec(cfg):
        pytest.skip("enc-dec covered by test_whisper_paths")
    params = LM.init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 12), jnp.int32)
    logits, aux = LM.forward(params, cfg, tokens)
    assert logits.shape == (2, 12, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-12b",
                                  "recurrentgemma-9b", "rwkv6-3b",
                                  "granite-moe-1b-a400m"])
def test_prefill_decode_matches_full_forward(arch):
    """Serving correctness: prefill(prompt) then decode_step per token
    must reproduce the teacher-forced forward logits."""
    cfg = R.get_config(arch, smoke=True)
    if cfg.n_experts:
        # capacity drops depend on total token count, so exact
        # prefix-consistency needs a drop-free capacity in this test
        # (decode itself always runs no-drop dispatch)
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
    params = LM.init_params(jax.random.key(1), cfg)
    S, extra = 12, 4
    tokens = jax.random.randint(jax.random.key(2), (2, S + extra), 0,
                                cfg.vocab)
    full_logits, _ = LM.forward(params, cfg, tokens)

    logits, cache = LM.prefill(params, cfg, tokens[:, :S], S + extra)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, S - 1]),
                               rtol=2e-2, atol=2e-2)
    for t in range(extra):
        logits, cache = LM.decode_step(params, cfg, cache,
                                       tokens[:, S + t: S + t + 1],
                                       jnp.asarray(S + t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, S + t]),
                                   rtol=2e-2, atol=2e-2)


def test_local_ring_buffer_matches_sliding_window():
    """Decode with the O(window) ring cache == full sliding-window
    attention (gemma3-style local layers)."""
    cfg = R.get_config("gemma3-12b", smoke=True)  # window 16 in smoke
    params = LM.init_params(jax.random.key(3), cfg)
    S = 40   # > 2x window: the ring has wrapped
    tokens = jax.random.randint(jax.random.key(4), (1, S), 0, cfg.vocab)
    full_logits, _ = LM.forward(params, cfg, tokens)
    logits, cache = LM.prefill(params, cfg, tokens[:, :S - 4], S)
    for t in range(S - 4, S):
        logits, cache = LM.decode_step(params, cfg, cache,
                                       tokens[:, t: t + 1],
                                       jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, t]),
                                   rtol=3e-2, atol=3e-2)


def test_moe_routing_is_topk_and_balanced_loss():
    from repro.models.layers import MoESpec, moe_apply, moe_init
    spec = MoESpec(n_experts=4, top_k=2, d_model=16, d_ff=32)
    p = moe_init(jax.random.key(0), spec, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16), jnp.float32)
    y, aux = moe_apply(p, spec, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.0   # aux loss well-defined


def test_moe_capacity_drops_overflow_gracefully():
    from repro.models.layers import MoESpec, moe_apply, moe_init
    spec = MoESpec(n_experts=2, top_k=2, d_model=8, d_ff=16,
                   capacity_factor=0.25)  # force drops
    p = moe_init(jax.random.key(0), spec, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 16, 8), jnp.float32)
    y, _ = moe_apply(p, spec, x)
    assert np.isfinite(np.asarray(y)).all()


def test_whisper_paths():
    from repro.models import encdec as ED
    cfg = R.get_config("whisper-tiny", smoke=True)
    params = ED.init_params(jax.random.key(0), cfg)
    frames = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                               jnp.float32)
    enc = ED.encode(params, cfg, frames)
    assert enc.shape == (2, 16, cfg.d_model)
    toks = jax.random.randint(jax.random.key(2), (2, 8), 0, cfg.vocab)
    logits = ED.decode_train(params, cfg, enc, toks)
    assert logits.shape == (2, 8, cfg.vocab)
    # decode loop against teacher forcing
    cache = ED.init_dec_cache(params, cfg, enc, 2, 8)
    for t in range(4):
        step_logits, cache = ED.decode_step(params, cfg, cache,
                                            toks[:, t: t + 1],
                                            jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(logits[:, t]),
                                   rtol=2e-2, atol=2e-2)


def test_rglru_decode_equals_scan():
    from repro.models import recurrent as RC
    p = RC.rglru_init(jax.random.key(0), 16, 16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 10, 16), jnp.float32)
    full, _ = RC.rglru_apply(p, x, None)
    state = RC.rglru_state_init(2, 16, dtype=jnp.float32)
    outs = []
    for t in range(10):
        o, state = RC.rglru_apply(p, x[:, t: t + 1], state)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=1e-3, atol=1e-3)


def test_rwkv_decode_equals_parallel():
    from repro.models import recurrent as RC
    p = RC.rwkv_init(jax.random.key(0), 16, 2, 32, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 9, 16), jnp.float32)
    full, _ = RC.rwkv_time_mix(p, 2, x, None)
    state = RC.rwkv_state_init(1, 16, 2, dtype=jnp.float32)
    outs = []
    for t in range(9):
        o, state = RC.rwkv_time_mix(p, 2, x[:, t: t + 1], state)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=1e-3, atol=1e-3)


def test_param_count_scales_with_depth():
    cfg = R.get_config("qwen2.5-3b", smoke=True)
    t1, _ = LM.param_count(cfg)
    t2, _ = LM.param_count(dataclasses.replace(cfg, n_layers=4))
    assert t2 > t1


def test_attention_gqa_grouping():
    from repro.models.layers import attention
    B, S, H, KH, hd = 1, 6, 4, 2, 8
    q = jax.random.normal(jax.random.key(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.key(1), (B, S, KH, hd))
    v = jax.random.normal(jax.random.key(2), (B, S, KH, hd))
    pos = jnp.arange(S)
    out = attention(q, k, v, pos, pos, causal=True)
    assert out.shape == (B, S, H, hd)
    # causality: output at t must not depend on future tokens
    v2 = v.at[:, -1].set(999.0)
    out2 = attention(q, k, v2, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :-1]),
                               np.asarray(out2[:, :-1]), rtol=1e-5)


def test_seq_parallel_flag_preserves_math():
    """seq_parallel only adds sharding constraints — single-device
    forward must be bit-identical to the baseline."""
    cfg = R.get_config("qwen2.5-3b", smoke=True)
    cfg_sp = dataclasses.replace(cfg, seq_parallel=True)
    params = LM.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    a, _ = LM.forward(params, cfg, toks)
    b, _ = LM.forward(params, cfg_sp, toks)
    assert np.array_equal(np.asarray(a), np.asarray(b))
