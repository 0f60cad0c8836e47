"""Algorithm 1 (weight mapping) invariants: unit tests, and each
property over a sweep of seeded random draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masking


def _init_theta_fan_in(n_in, n_out, fi, seed):
    tl = masking.init_theta_layer(jax.random.key(seed), n_in, n_out,
                                  initial_fan_in=fi)
    fan = np.asarray(tl.fan_in())
    assert (fan == min(fi, n_in)).all()
    # signs are exactly +-1; theta non-negative at init
    assert set(np.unique(np.asarray(tl.sign))) <= {-1.0, 1.0}
    assert (np.asarray(tl.theta) >= 0).all()


def _random_mask_exact_fan_in(n_in, n_out, f, seed):
    m = masking.random_mask(jax.random.key(seed), n_in, n_out, f)
    assert m.shape == (n_in, n_out)
    assert (np.asarray(m.sum(0)) == min(f, n_in)).all()


def _final_mask_topk_exact(n_in, n_out, f, seed):
    theta = jax.random.uniform(jax.random.key(seed), (n_in, n_out))
    m = np.asarray(masking.final_mask(theta, f))
    assert (m.sum(0) == min(f, n_in)).all()
    # selected entries are the top-f thetas per column
    th = np.asarray(theta)
    for c in range(n_out):
        sel = th[:, c][m[:, c] > 0]
        unsel = th[:, c][m[:, c] == 0]
        if len(unsel):
            assert sel.min() >= unsel.max() - 1e-6


def _property_draws(n):
    """n seeded argument draws, one per property, each over the full
    range the property allows."""
    rng = np.random.default_rng(0)
    return [((int(rng.integers(2, 65)), int(rng.integers(1, 17)),
              int(rng.integers(1, 65)), int(rng.integers(0, 2 ** 16))),
             (int(rng.integers(2, 49)), int(rng.integers(1, 13)),
              int(rng.integers(1, 9)), int(rng.integers(0, 1000))),
             (int(rng.integers(4, 41)), int(rng.integers(1, 11)),
              int(rng.integers(1, 7)), int(rng.integers(0, 1000))))
            for _ in range(n)]


@pytest.mark.parametrize("theta_args,random_args,final_args",
                         _property_draws(30),
                         ids=[f"draw{i}" for i in range(30)])
def test_masking_properties_seeded(theta_args, random_args, final_args):
    _init_theta_fan_in(*theta_args)
    _random_mask_exact_fan_in(*random_args)
    _final_mask_topk_exact(*final_args)


def test_init_dense_when_none():
    tl = masking.init_theta_layer(jax.random.key(0), 10, 3, None)
    assert (np.asarray(tl.fan_in()) == 10).all()


def test_effective_weight_gates_value_and_grad():
    theta = jnp.asarray([[0.5, -0.2], [0.0, 1.0]])
    sign = jnp.asarray([[1.0, -1.0], [1.0, -1.0]])
    w = masking.effective_weight(theta, sign)
    # w = theta * sign * 1(theta > 0)
    assert np.allclose(np.asarray(w), [[0.5, 0.0], [0.0, -1.0]])
    # gradient flows only through active connections (Alg. 2 line 5)
    g = jax.grad(lambda t: jnp.sum(masking.effective_weight(t, sign) ** 2)
                 )(theta)
    assert float(g[0, 1]) == 0.0 and float(g[1, 0]) == 0.0
    assert float(g[0, 0]) != 0.0 and float(g[1, 1]) != 0.0


def test_mask_to_indices_points_at_active_rows():
    mask = jnp.asarray([[1, 0], [0, 1], [1, 1], [0, 0]], jnp.float32)
    idx = np.asarray(masking.mask_to_indices(mask, 2))  # (n_out=2, F=2)
    assert idx.shape == (2, 2)
    for c in range(2):
        active = {r for r in range(4) if float(mask[r, c]) > 0}
        assert set(idx[c]) <= active
        assert set(idx[c]) == active  # exactly-F columns keep all actives


def test_final_mask_tie_break_deterministic_at_o1_thetas():
    """Exact theta ties at O(1) values select the LOWER input index,
    deterministically.  The previous value-space nudge
    (``theta + tie * 1e-9``) underflows in float32 against O(1) thetas
    (1.0 + 5e-10 == 1.0), so tie selection silently depended on the
    backend's sort order; the rank-space stable argsort cannot."""
    n_in, n_out = 64, 16
    theta = jnp.ones((n_in, n_out), jnp.float32)       # every entry tied
    m = np.asarray(masking.final_mask(theta, 2))
    assert (m.sum(0) == 2).all()
    # lower-index wins: rows 0 and 1 in every column
    assert (m[:2] == 1).all() and (m[2:] == 0).all()

    # repeated calls agree bit-for-bit (and under jit)
    m2 = np.asarray(jax.jit(lambda t: masking.final_mask(t, 2))(theta))
    assert (m == m2).all()

    # mixed case: ties only among a subset, at a magnitude where the
    # old 1e-9 nudge underflows
    theta = jnp.zeros((8, 1), jnp.float32).at[2:6, 0].set(1.0)
    m = np.asarray(masking.final_mask(theta, 2))
    assert m[:, 0].tolist() == [0, 0, 1, 1, 0, 0, 0, 0]
