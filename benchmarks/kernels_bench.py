"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs jnp reference.

On this CPU container the numbers are NOT TPU performance — they only
prove the kernels run and give the ref-vs-kernel shape sweep a timing
column.  TPU roofline expectations are derived analytically in
EXPERIMENTS.md (section Perf, wkv6 entry).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_table, timed
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.kernels.lut_gather import ops as lg_ops, ref as lg_ref
from repro.kernels.masked_matmul import ops as mm_ops, ref as mm_ref
from repro.kernels.wkv6 import ref as wkv_ref


def run(fast: bool = False):
    rows = []
    # wkv6 chunked-ref timing across chunk sizes (the kernel's tuning knob)
    B, S, H, K = 1, 512, 4, 64
    ks = jax.random.split(jax.random.key(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.3 - 2.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    for chunk in (16, 64, 128):
        f = jax.jit(lambda a, b, c, d, e: wkv_ref.wkv_chunked(
            a, b, c, d, e, None, chunk=chunk))
        t = timed(f, r, k, v, lw, u, iters=2)
        rows.append(["wkv6_chunked_ref", f"S={S},C={chunk}",
                     f"{t*1e3:.1f}ms"])

    # masked matmul: gather-ref vs dense-scatter formulation
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (1024, 784))
    conn = jax.random.randint(ks[1], (256, 6), 0, 784)
    w = jax.random.normal(ks[2], (256, 6))
    f_g = jax.jit(lambda a: mm_ref.masked_matmul(a, conn, w))
    f_d = jax.jit(lambda a: mm_ref.masked_matmul_dense(a, conn, w, 784))
    rows.append(["masked_matmul", "gather-form (dense-small)",
                 f"{timed(f_g, x, iters=3)*1e3:.2f}ms"])
    rows.append(["masked_matmul", "scatter-form (sparse-large)",
                 f"{timed(f_d, x, iters=3)*1e3:.2f}ms"])

    # lut_gather smoke rows: per-layer vs fused, packed vs int32 (the
    # canonical tracked comparison lives in benchmarks/lut_infer_bench)
    spec = LD.ModelSpec(name="bench", in_features=16,
                        widths=(64, 32, 32, 5), bits=2, fan_in=3,
                        degree=1, adder_width=2)
    model = LD.init_model(jax.random.key(2), spec)
    packed = LS.synthesise(model, spec, pack=True)
    legacy = LS.synthesise(model, spec, pack=False)
    B = 1024 if fast else 2048
    codes = jax.random.randint(jax.random.key(3), (B, 16), 0, 4
                               ).astype(jnp.int32)
    def f_seed(c):             # the seed's broadcast layout, as jnp
        for t in legacy:
            c = lg_ref.lut_layer(c, t.conn, t.sub_table, t.add_table,
                                 t.in_bits, t.sub_bits)
        return c

    f_seed = jax.jit(f_seed)
    f_pl = jax.jit(lambda c: lg_ops.lut_network(packed, c))
    f_fused = lg_ops.make_network_fn(packed, fused=True, block_b=B)
    assert np.array_equal(np.asarray(f_fused(codes)),
                          np.asarray(f_seed(codes)))
    rows.append(["lut_gather", f"int32 bcast jnp oracle (seed layout), B={B}",
                 f"{timed(f_seed, codes, iters=3)*1e3:.2f}ms"])
    rows.append(["lut_gather", f"per-layer uint8 flat, B={B}",
                 f"{timed(f_pl, codes, iters=3)*1e3:.2f}ms"])
    rows.append(["lut_gather", f"fused uint8 single-kernel, B={B}",
                 f"{timed(f_fused, codes, iters=3)*1e3:.2f}ms"])

    print_table("Kernel micro-bench (CPU; relative only)",
                ["kernel", "config", "time"], rows)
    return {"rows": rows}


if __name__ == "__main__":
    run()
