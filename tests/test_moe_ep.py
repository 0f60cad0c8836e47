"""Expert-parallel MoE dispatch (shard_map) — numerical equivalence
against the GSPMD capacity path, outputs AND gradients.

Runs in a subprocess so its 4-device mesh and XLA flags stay isolated
from the main test process (which pins its own virtual-device count in
conftest.py before jax initialises).
"""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.layers import MoESpec, moe_init, moe_apply, moe_apply_ep

    mesh = jax.make_mesh((2, 2), ('data', 'model'))
    spec = MoESpec(n_experts=8, top_k=2, d_model=16, d_ff=32,
                   capacity_factor=8.0)
    p = moe_init(jax.random.key(0), spec, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 8, 16), jnp.float32)

    with mesh:
        def loss_ref(p, x):
            y, aux = moe_apply(p, spec, x, no_drop=True)
            return jnp.sum(y ** 2) + aux, y

        def loss_ep(p, x):
            y, aux = moe_apply_ep(p, spec, x, mesh, no_drop=True)
            return jnp.sum(y ** 2) + aux, y

        (l0, y0), g0 = jax.jit(jax.value_and_grad(loss_ref, has_aux=True))(p, x)
        (l1, y1), g1 = jax.jit(jax.value_and_grad(loss_ep, has_aux=True))(p, x)

    assert abs(float(l0) - float(l1)) < 1e-3, (float(l0), float(l1))
    assert np.abs(np.asarray(y0) - np.asarray(y1)).max() < 1e-4
    for k in ('w_in', 'w_gate', 'w_out'):
        d = np.abs(np.asarray(g0['experts'][k])
                   - np.asarray(g1['experts'][k])).max()
        assert d < 1e-4, (k, d)
    d = np.abs(np.asarray(g0['router']['w'])
               - np.asarray(g1['router']['w'])).max()
    assert d < 1e-4, ('router', d)
    print('EP-OK')
""")


def test_moe_ep_matches_reference_on_4_devices():
    # timeout inside the 240 s per-test limit (tests/time_limit.py)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=200)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EP-OK" in r.stdout
