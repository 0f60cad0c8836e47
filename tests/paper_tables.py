"""Random LUT tables at the paper rows' shapes, shared by the
compile-only checks (``test_tpu_compile.py``), the paper-width
exactness test (``test_lut_fused.py``) and the wide-input tests
(``test_wide_input.py``)."""
import numpy as np

import jax.numpy as jnp

from repro.core import lut_synth as LS
from repro.kernels.lut_gather.lut_gather import (MATMUL_ROUTE_MAX_BITS,
                                                 routing_matrix)


# the benchmark's over-budget network: plans to 5 int4 fused segments
DEEPER_WIDER_3X = dict(in_features=16, widths=(512,) * 6 + (5,), bits=2,
                       fan_in=6, adder_width=2)


def shaped_tables(spec, seed: int = 0):
    """Random LayerTables with the shapes and dtypes ``synthesise``
    emits for ``spec`` (codes kept inside each slab's bit width, so
    ``pack_tables_int4`` applies where synthesis output would)."""
    rng = np.random.default_rng(seed)
    out = []
    for s in spec.layer_specs():
        A, F, b_in = s.adder_width, s.fan_in, s.in_quant.bits
        conn = rng.integers(0, s.n_in, (s.n_out, A, F)).astype(np.int32)
        K = 2 ** (b_in * F)
        wide = 16 if s.is_output else s.out_quant.bits
        if A > 1:
            sub_bits = s.sub_quant.bits
            sub = rng.integers(0, 2 ** sub_bits, (s.n_out, A, K)).astype(
                LS.table_dtype_for(sub_bits))
            add = rng.integers(0, 2 ** min(wide, 15),
                               (s.n_out, 2 ** (A * sub_bits))).astype(
                LS.table_dtype_for(wide))
        else:
            sub_bits = s.out_quant.bits
            sub = rng.integers(0, 2 ** min(wide, 15), (s.n_out, 1, K)
                               ).astype(LS.table_dtype_for(wide))
            add = np.zeros((s.n_out, 0), sub.dtype)
        out.append(LS.LayerTables(
            conn=jnp.asarray(conn), sub_table=jnp.asarray(sub),
            add_table=jnp.asarray(add), in_bits=b_in, sub_bits=sub_bits,
            out_bits=s.out_quant.bits, fan_in=F, adder_width=A,
            is_output=s.is_output, out_quant=s.out_quant,
            sub_quant=s.sub_quant, table_dtype=jnp.dtype(sub.dtype),
            routing=(routing_matrix(conn, b_in, s.n_in)
                     if b_in * F <= MATMUL_ROUTE_MAX_BITS else None)))
    return out
