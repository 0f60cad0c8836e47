"""int8 KV cache: serving-path equivalence within quantization noise."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm as LM
from repro.models import registry as R


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma3-12b"])
def test_int8_cache_matches_bf16_within_quant_noise(arch):
    cfg = R.get_config(arch, smoke=True)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = LM.init_params(jax.random.key(1), cfg)
    S, extra = 12, 4
    toks = jax.random.randint(jax.random.key(2), (2, S + extra), 0,
                              cfg.vocab)
    full, _ = LM.forward(params, cfg, toks)
    logits, cache = LM.prefill(params, cfg8, toks[:, :S], S + extra)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, S - 1]), atol=0.15)
    for t in range(extra):
        logits, cache = LM.decode_step(params, cfg8, cache,
                                       toks[:, S + t: S + t + 1],
                                       jnp.asarray(S + t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, S + t]), atol=0.15)


def test_int8_cache_halves_storage():
    cfg = dataclasses.replace(R.get_config("qwen2.5-3b", smoke=True),
                              kv_cache_dtype="int8")
    cache = LM.init_cache(cfg, batch=2, max_len=64)
    leaf = cache["stacks"][0]["k"]
    assert leaf.dtype == jnp.int8
    scales = cache["stacks"][0]["k_s"]
    assert scales.dtype == jnp.float16
    # int8 codes + fp16 scales ~= 0.5x + hd-fraction of bf16 cache
    bf16 = LM.init_cache(R.get_config("qwen2.5-3b", smoke=True), 2, 64)
    b_int8 = leaf.nbytes + scales.nbytes
    b_bf16 = bf16["stacks"][0]["k"].nbytes
    assert b_int8 < 0.6 * b_bf16
