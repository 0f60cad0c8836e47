"""Pallas TPU kernels for LUT-mode inference (truth-table gather).

This is the TPU re-think of the paper's inference substrate.  On the
FPGA, each neuron's transfer function is *burned into* 6-LUT fabric:
lookup is free, routing is free, and the cost is area.  On a TPU the
same artefact — per-neuron truth tables — becomes data resident in
HBM/VMEM, and inference becomes integer gathers:

  1. gather the F fan-in codes per (neuron, sub-neuron)   [routing]
  2. bit-pack them into a table index (slot 0 = low bits) [address]
  3. per-neuron table lookup                              [the LUT]
  4. A > 1: pack the A sub-codes, look up the adder table [PolyLUT-Add]

Every kernel runs FEATURE-MAJOR: activation codes are (features,
batch) with the batch on the 128 vector lanes, and each layer is
computed 128 batch lanes at a time.  The TPU's vector gather
(``jnp.take_along_axis`` along the lane axis) reads one 128-lane row of
32-bit elements per output row, so a (neuron, sub-neuron) table row of
Ks stored elements is looked up in ceil(Ks/128) lane-gathers, each
followed by a select on the chunk number; byte tables are read as
32-bit words of four bytes where that cuts the chunks (*Slab packing*),
and no (TB, TN, A, K) broadcast of the tables is ever built.
Routing and the adder-address pack are small float32 matmuls on the
MXU, exact while the packed address stays under 2**24:

* ``lut_gather_pallas`` — one layer per ``pallas_call``, grid over
  (batch tiles, neuron tiles).  Activation codes round-trip through HBM
  between layers.
* ``lut_network_fused_pallas`` — the whole synthesised network in a
  SINGLE ``pallas_call``.  Grid over batch tiles only; every layer's
  route/sub/add slabs are kernel inputs resident in VMEM; inter-layer
  activation codes live in a ``(stage_rows, TB)`` VMEM scratch buffer.
  A forward pass therefore does ONE HBM read of the input codes and
  ONE HBM write of the output codes.

K = 2**(b_in * F) is the whole point of the paper: PolyLUT-Add keeps K
small (A * 2**(b*F) + 2**(A(b+1)) instead of 2**(b*F*A)), which is
precisely what lets the *entire network's* tables sit in VMEM at once.
With packed uint8 tables (core/lut_synth emits uint8 whenever output
codes fit 8 bits — every paper config; the seed stored int32):

    beta=2, F=6, A=2, width 32 per layer:
        sub tables  32 * 2 * 4096 * 1 B = 256 KB / layer   (int32: 1 MB)
        add tables  32 * 2**6   * 1 B   =   2 KB / layer
        conn        32 * 2 * 6 * 4 B    = 1.5 KB / layer
    -> a 4-layer network is ~1 MB of VMEM, comfortably inside the
       ~16 MB/core budget next to a (width, 256) int32 activation
       scratch; the equivalent fan-in-12 flat LUT would need
       32 * 2**24 B = 512 MB *per layer* and cannot fit.

So the architectural contribution of the paper maps 1:1 onto the TPU
memory hierarchy: the Add-structure + uint8 packing is what keeps the
whole network VMEM-resident, and fusion is what converts that residency
into bandwidth savings.  The lookups are lane gathers and selects on
the VPU; the only MXU work is routing — LUT inference is gather-bound
on TPU, and the roofline comparison LUT-vs-matmul inference is reported
by benchmarks/table8_cost_model.py.

Memory layout
-------------

**Slab packing.**  Each layer contributes three VMEM-resident inputs:
the route (the (n_in, TN*A) float32 routing matrix, or the (TN, A, F)
int32 conn when matmul routing is off), the (TN, A, K) sub-table slab,
and the (TN, Ka) adder slab.  The wrappers view the sub slab as
(TN*A, K) — row ``n*A + a`` is one sub-neuron's table — and the conn
as (TN*A, F), both free row-major reshapes.  Slabs whose codes fit
4 bits may arrive int4 NIBBLE-PACKED — two codes per byte, low nibble
first, table axis halved to (TN, A, K//2) — the same
two-codes-per-byte layout repro/artifact persists on disk, so a
cold-loaded ``encoding: int4`` slab flows into the kernel with no
expansion anywhere.  K = 2**(b_in*F) and Ka = 2**(A*b_sub) are always
even, so slab rows never straddle a byte.  Packing halves table
residency, which is exactly the ``ops.fused_vmem_bytes`` term that
gates fusion eligibility (``ops.can_fuse``).

A byte slab (uint8, or int4 nibble bytes) whose rows span more 128-lane
chunks as bytes than as 32-bit words — ceil(Ks/128) > ceil(Ks/512),
i.e. rows over 128 bytes — is bound as WORDS: ``lookup_slab`` repacks
each row's bytes with explicit shifts, ``b0 | b1<<8 | b2<<16 | b3<<24``,
into a (rows, Ks/4) int32 array, once on the host when the engine is
built (in the graph only for traced slabs).  The bytes, their order and
the VMEM claim are the same; a gathered lane now carries four bytes
instead of one widened byte, so a 4,096-entry uint8 adder row costs 8
gathers instead of 32.  Other slabs (int32, or rows of at most 128
bytes) are bound as stored.  One rule then reads every layout: a slab
element holds e = (element bits) / (entry bits) entries, lowest first
(1 for whole uint8 or int32 entries, 2 for nibble bytes, 4 or 8 for
words of bytes or nibbles); entry ``i`` lives in element ``i >> log2(e)``
and is extracted after the gather as
``(elem >> bits*(i & (e-1))) & (2**bits - 1)``.

**Scratch staging.**  The fused kernel stages inter-layer activation
codes through ONE (stage_rows, TB) int32 VMEM scratch buffer, as tall
as the widest hidden layer: layer l reads ``scratch[:n_in, lanes]`` and
writes ``scratch[:n_out, lanes]``; the first layer reads the in block
and the last writes the out block directly.

**Tile pipeline.**  The batch tile TB is a whole number of 128-lane
groups (``batch_tile``).  ``pipeline=False`` (grid mode): the batch
axis is a pallas grid, one (n_in, TB) block in / (n_out, TB) block out
per step, tables bound once as whole VMEM arrays.  ``pipeline=True``
(double-buffered mode): the kernel runs as a SINGLE grid step with the
codes/out refs left in HBM (``memory_space=ANY``) and drives its own
tile loop with async DMA — two (n_in, TB) in-slots, two (n_out, TB)
out-slots, and a pair of DMA semaphore arrays.  Step i starts the copy
of tile i+1 before waiting on tile i, and an out-slot is reclaimed only
after tile i-2's store has landed, so the HBM transfers of neighbouring
tiles overlap the current tile's compute instead of serialising on one
buffer pair.

Segmented execution
-------------------

A network whose slabs exceed the fused VMEM budget no longer falls off
a cliff onto the per-layer path.  ``ops.plan_segments`` partitions the
layer list into the FEWEST contiguous segments whose
``ops.fused_vmem_bytes`` each fit the budget (cost-model tiebreak:
among minimum-count partitions, cut where the layer is narrowest,
because the cut layer's code vector is the only data that crosses HBM
between segments — ``2 * B * width * 4`` bytes per cut per forward
pass, one store + one load).  ``ops.lut_network_segmented`` then runs
the plan as a CHAIN of these fused kernels: within a segment the
inter-layer codes never leave the VMEM scratch; between segments the
code tensor is an ordinary HBM array, which is exactly the layout the
double-buffered mode above stages — so multi-segment plans default to
``pipeline=True`` per segment and each segment's tile DMAs overlap its
compute while its slabs stay resident.  One segment IS the classic
fully fused path (bit-identical, same artifact id semantics); the
per-layer engine survives only as the last resort when a single layer
alone cannot fit.  The planner also tries int4 nibble-packing (see
*Slab packing*) and adopts it when the halved residency reduces the
segment count.  Plans serialise into the artifact manifest
(``SegmentPlan.summary()``) together with the per-segment tuned
``block_b``, so a cold-loaded model skips both re-planning and the
``tune_block_b`` sweep.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# packed table addresses above this width lose f32-matmul exactness
# headroom (and the tables could never fit VMEM anyway)
MATMUL_ROUTE_MAX_BITS = 20

# the double-buffered kernel unrolls its tile loop (static slot
# indices) up to this many tiles; beyond it a rolled fori_loop bounds
# program size at the cost of dynamic slot slicing
PIPELINE_UNROLL_MAX_TILES = 32

# vector lanes: the batch axis of every kernel block, and the row width
# one lane-gather can read
LANES = 128

# scoped-VMEM floor and ceiling handed to the compiler: the default
# scoped limit is below what the larger fused plans bind, and the
# ceiling stays under the 128 MiB of VMEM of a v5e/v6e core
_VMEM_LIMIT_FLOOR = 32 * 2 ** 20
_VMEM_LIMIT_CEIL = 100 * 2 ** 20


def batch_tile(block_b: int, batch: Optional[int] = None) -> int:
    """Kernel batch tile for a requested ``block_b`` (clamped to
    ``batch`` when given): rounded up to whole 128-lane groups, since
    the batch rides the lane axis."""
    tb = block_b if batch is None else min(block_b, batch)
    return -(-max(tb, 1) // LANES) * LANES


def slot_rows(n: int) -> int:
    """Rows of a double-buffered DMA slot holding ``n`` code rows:
    whole 8-row sublane tiles, since a DMA moves whole tiles."""
    return -(-n // 8) * 8


def stage_rows(widths) -> int:
    """Rows of the fused kernel's activation scratch for a segment whose
    layers have output ``widths``: every output but the last is staged
    there (one unused row for a single-layer segment)."""
    return max(widths[:-1], default=1)


def _compiler_params(vmem_bytes: int):
    limit = min(max(2 * vmem_bytes, _VMEM_LIMIT_FLOOR), _VMEM_LIMIT_CEIL)
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def routing_matrix(conn, in_bits: int, n_in: int) -> jnp.ndarray:
    """Fold routing + bit-packing into one matrix.

    W[i, n*A + a] = sum_f [conn[n, a, f] == i] * 2**(in_bits * f), so
    the packed table address of every (neuron, sub-neuron) is a single
    product ``W^T @ codes`` — the gather of F fan-in codes and the
    shift/add collapse into one (TN*A, n_in) x (n_in, 128) matmul that
    runs on the MXU (BLAS on CPU).  Exact in float32 while the packed
    address stays under 2**24 (guarded by MATMUL_ROUTE_MAX_BITS).
    Repeated fan-in features sum their place values, which matches the
    shift/add packing exactly.
    """
    conn_np = np.asarray(conn)
    n_out, A, F = conn_np.shape
    w = np.zeros((n_in, n_out * A), np.float32)
    flat = conn_np.reshape(n_out * A, F)
    cols = np.arange(n_out * A)
    for f in range(F):
        np.add.at(w, (flat[:, f], cols), float(1 << (in_bits * f)))
    return jnp.asarray(w)


def _dot(a, b, contract_a: int = 1):
    """Exact float32 matmul contracting ``a``'s axis ``contract_a``
    with ``b``'s axis 0 (HIGHEST: the default MXU precision truncates
    f32 operands to bf16, which mangles place values like 2**0 + 2**10
    that arise when a fan-in feature repeats)."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _route(act, route, in_bits: int, matmul_route: bool):
    """Packed table address of every (neuron, sub-neuron) row.
    act: (n_in, L) int32; route: the (n_in, M) routing matrix, or the
    (M, F) int32 conn -> (M, L) int32, rows ``n*A + a``, slot 0 in
    the low bits."""
    x = act.astype(jnp.float32)
    if matmul_route:
        return _dot(route, x, contract_a=0).astype(jnp.int32)
    # gather form: one 0/1 selection matmul per fan-in slot
    M, F = route.shape
    feat = jax.lax.broadcasted_iota(jnp.int32, (M, act.shape[0]), 1)
    idx = jnp.zeros((M, act.shape[1]), jnp.int32)
    for f in range(F):
        sel = (route[:, f:f + 1] == feat).astype(jnp.float32)
        idx = idx + (_dot(sel, x).astype(jnp.int32) << (in_bits * f))
    return idx


def lane_chunks(width: int) -> int:
    """128-lane gathers that read a slab row of ``width`` elements."""
    return max(1, -(-width // LANES))


def word_packs(width: int, itemsize: int) -> bool:
    """Whether a slab row of ``width`` elements of ``itemsize`` bytes is
    looked up as 32-bit words: byte slabs only, whole words only, and
    only where the words span fewer 128-lane chunks than the bytes."""
    return (itemsize == 1 and width % 4 == 0
            and lane_chunks(width) > lane_chunks(width // 4))


def lookup_slab(slab, nibbles: bool):
    """The array ``_lookup`` reads for a table slab, and the bit width of
    one entry in it: ``(array, bits)``.

    slab: (..., Ks) uint8 or int32, one entry per element, or two per
    byte when ``nibbles`` (int4, low nibble first).  Where
    ``word_packs`` holds, each row's bytes become int32 words
    ``b0 | b1 << 8 | b2 << 16 | b3 << 24`` (shape (..., Ks // 4)): the
    same bytes, in the same order on every backend.  Numpy repacks a
    concrete slab once; a traced slab is repacked in the graph."""
    bits = 4 if nibbles else 8 * slab.dtype.itemsize
    width = slab.shape[-1]
    if not word_packs(width, slab.dtype.itemsize):
        return slab, bits
    xp = jnp if isinstance(slab, jax.core.Tracer) else np
    b = xp.asarray(slab).astype(xp.int32).reshape(
        slab.shape[:-1] + (width // 4, 4))
    words = b[..., 0] | b[..., 1] << 8 | b[..., 2] << 16 | b[..., 3] << 24
    return jnp.asarray(words), bits


def lookup_row_chunks(shape, itemsize: int) -> int:
    """Lane-gather row-chunks of one lookup per 128-row batch group in a
    slab of ``shape`` (rows ``shape[:-1]``, ``shape[-1]`` elements of
    ``itemsize`` bytes per row), as ``lookup_slab`` lays it out."""
    width = shape[-1]
    if word_packs(width, itemsize):
        width //= 4
    return int(np.prod(shape[:-1])) * lane_chunks(width)


def _lookup(tab_ref, pos, bits: int):
    """Row-wise table lookup: ``out[r, j] = table[r, pos[r, j]]``.
    tab_ref: (R, Ks) slab from ``lookup_slab`` whose elements each hold
    e = (element bits) / ``bits`` entries, lowest first: e = 1 for a
    uint8 or int32 slab of whole entries, 2 for int4 nibble bytes, 4 or
    8 for int32 words of bytes or nibbles; pos: (R, L) int32 logical
    entries -> (R, L) int32.

    Entry ``pos`` lives in element ``pos >> log2(e)``, field
    ``pos & (e - 1)``.  The row is read 128 elements at a time: each
    chunk is one lane-gather over every row, kept where the element
    falls in that chunk; the field is extracted once, after the select
    loop (``(out >> field * bits) & (2**bits - 1)``: the shift of an
    int32 word is arithmetic, the mask makes it exact).  Rows narrower
    than 128 lanes are zero-padded in VMEM."""
    Ks = tab_ref.shape[1]
    log_e = (8 * tab_ref.dtype.itemsize // bits).bit_length() - 1
    elem = pos >> log_e

    def gather(t, lo):
        t = t.astype(jnp.int32)                      # no-op on words
        if t.shape[1] < LANES:
            t = jnp.pad(t, ((0, 0), (0, LANES - t.shape[1])))
        return jnp.take_along_axis(t, lo, axis=1)

    if Ks <= LANES:
        out = gather(tab_ref[...], elem)
    else:
        n_full, tail = divmod(Ks, LANES)
        lo, hi = elem & (LANES - 1), elem >> 7       # elem // LANES

        def chunk(c, acc):
            start = pl.multiple_of(c * LANES, LANES)
            g = gather(tab_ref[:, pl.ds(start, LANES)], lo)
            return jnp.where(hi == c, g, acc)

        out = jax.lax.fori_loop(0, n_full, chunk, jnp.zeros_like(pos))
        if tail:
            g = gather(tab_ref[:, n_full * LANES:], lo)
            out = jnp.where(hi == n_full, g, out)
    if log_e:
        field = pos & ((1 << log_e) - 1)
        out = (out >> (field * bits)) & ((1 << bits) - 1)
    return out


def _layer_compute(act, route_ref, sub_ref, add_ref, *, in_bits: int,
                   sub_bits: int, use_adder: bool, matmul_route: bool,
                   sub_field: int, add_field: int):
    """One LUT layer on one 128-lane batch group.

    act: (n_in, L) int32 codes; route_ref: (n_in, TN*A) float32
    routing matrix when ``matmul_route``, else (TN*A, F) int32 conn;
    sub_ref: (TN*A, Ks) sub-table slab from ``lookup_slab``, row
    ``n*A + a``, entries ``sub_field`` bits wide; add_ref: (TN, Kas)
    adder slab, entries ``add_field`` bits wide.  Returns (TN, L) int32.
    """
    idx = _route(act, route_ref[...], in_bits, matmul_route)
    sub = _lookup(sub_ref, idx, sub_field)                   # (TN*A, L)
    if not use_adder:
        return sub
    # PolyLUT-Add: pack each neuron's A sub-codes (rows n*A + a) into
    # its adder address with a (TN, TN*A) place-value matmul
    TN = add_ref.shape[0]
    M = sub.shape[0]
    A = M // TN
    row = jax.lax.broadcasted_iota(jnp.int32, (TN, M), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (TN, M), 1)
    place = jnp.zeros((TN, M), jnp.float32)
    for a in range(A):
        place = jnp.where(col == row * A + a,
                          float(1 << (sub_bits * a)), place)
    aidx = _dot(place, sub.astype(jnp.float32)).astype(jnp.int32)
    return _lookup(add_ref, aidx, add_field)


def _for_lane_groups(n_lanes: int, body):
    """Run ``body(cols)`` over every 128-lane group of a tile."""
    def step(j, carry):
        body(pl.ds(pl.multiple_of(j * LANES, LANES), LANES))
        return carry
    jax.lax.fori_loop(0, n_lanes // LANES, step, 0)


def _lut_kernel(codes_ref, conn_ref, sub_ref, add_ref, out_ref,
                *, in_bits: int, sub_bits: int, use_adder: bool,
                sub_field: int, add_field: int):
    def body(cols):
        out_ref[:, cols] = _layer_compute(
            codes_ref[:, cols], conn_ref, sub_ref, add_ref,
            in_bits=in_bits, sub_bits=sub_bits, use_adder=use_adder,
            matmul_route=False, sub_field=sub_field, add_field=add_field)

    _for_lane_groups(codes_ref.shape[1], body)


def dummy_add_table(n_rows: int, dtype) -> jnp.ndarray:
    """Zero-width-safe stand-in for an adder-off layer's add table:
    Pallas cannot bind a (n, 0) block, so every engine binds this
    1-entry-per-row dummy instead and statically skips the adder path
    (``use_adder`` must be derived BEFORE substituting it — a dummy is
    never packed and never read)."""
    return jnp.zeros((n_rows, 1), dtype)


def _nbytes(shape, dtype) -> int:
    return int(np.prod(shape)) * jnp.dtype(dtype).itemsize


def lut_gather_pallas(codes: jnp.ndarray, conn: jnp.ndarray,
                      sub_table: jnp.ndarray, add_table: jnp.ndarray,
                      in_bits: int, sub_bits: int,
                      block_b: int = 256, block_n: int = 32,
                      interpret: bool = False,
                      sub_packed: bool = False,
                      add_packed: bool = False) -> jnp.ndarray:
    """codes: (B, n_in) int32 activation codes on this layer's grid;
    conn: (n_out, A, F); sub_table: (n_out, A, K) uint8 or int32;
    add_table: (n_out, Ka), Ka == 0 disables the adder path.
    ``sub_packed`` / ``add_packed`` declare int4 nibble-packed slabs
    (table axis halved, unpacked in-kernel).  Returns (B, n_out) int32.
    Neuron tiles of ``block_n`` (a multiple of 32, the uint8 sublane
    tile) sit on the sublane axis of every block.  The slabs are laid
    out by ``lookup_slab`` before the jitted call.
    """
    # an adder-off layer's add slab is by definition unread, so its
    # packing flag is forced off
    sub_table, sub_field = lookup_slab(sub_table, sub_packed)
    add_table, add_field = lookup_slab(
        add_table, add_packed and add_table.shape[-1] > 0)
    return _lut_gather_call(codes, conn, sub_table, add_table,
                            in_bits=in_bits, sub_bits=sub_bits,
                            block_b=block_b, block_n=block_n,
                            interpret=interpret, sub_field=sub_field,
                            add_field=add_field)


@functools.partial(jax.jit, static_argnames=("in_bits", "sub_bits",
                                             "block_b", "block_n",
                                             "interpret",
                                             "sub_field", "add_field"))
def _lut_gather_call(codes, conn, sub_table, add_table, *, in_bits: int,
                     sub_bits: int, block_b: int, block_n: int,
                     interpret: bool, sub_field: int, add_field: int):
    B, n_in = codes.shape
    n_out, A, F = conn.shape
    # adder on/off is decided by the REAL table's width, before the
    # zero-width dummy is substituted
    use_adder = add_table.shape[-1] > 0

    TB = batch_tile(block_b, B)
    TN = n_out if n_out <= block_n else block_n
    pad_b = (-B) % TB
    pad_n = (-n_out) % TN
    if not use_adder:      # zero-width-safe: bind the 1-entry dummy
        add_table = dummy_add_table(n_out, sub_table.dtype)
    if pad_n:
        conn = jnp.pad(conn, ((0, pad_n), (0, 0), (0, 0)))
        sub_table = jnp.pad(sub_table, ((0, pad_n), (0, 0), (0, 0)))
        add_table = jnp.pad(add_table, ((0, pad_n), (0, 0)))
    Bp, Np = B + pad_b, n_out + pad_n
    Ks, Kas = sub_table.shape[-1], add_table.shape[-1]
    codes_t = jnp.pad(codes, ((0, pad_b), (0, 0))).T         # (n_in, Bp)

    kernel = functools.partial(_lut_kernel, in_bits=in_bits,
                               sub_bits=sub_bits, use_adder=use_adder,
                               sub_field=sub_field, add_field=add_field)
    blocks = [((n_in, TB), jnp.int32), ((TN * A, F), conn.dtype),
              ((TN * A, Ks), sub_table.dtype), ((TN, Kas), add_table.dtype),
              ((TN, TB), jnp.int32)]
    out = pl.pallas_call(
        kernel,
        grid=(Bp // TB, Np // TN),
        in_specs=[
            pl.BlockSpec((n_in, TB), lambda i, j: (0, i)),
            pl.BlockSpec((TN * A, F), lambda i, j: (j, 0)),
            pl.BlockSpec((TN * A, Ks), lambda i, j: (j, 0)),
            pl.BlockSpec((TN, Kas), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((TN, TB), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((Np, Bp), jnp.int32),
        compiler_params=_compiler_params(
            2 * sum(_nbytes(s, d) for s, d in blocks)),
        interpret=interpret,
    )(codes_t, conn.reshape(Np * A, F), sub_table.reshape(Np * A, Ks),
      add_table)
    return out[:n_out, :B].T


# --------------------------------------------------------------------------
# Fused multi-layer engine: the whole network in one pallas_call
# --------------------------------------------------------------------------

def _run_layers(refs, metas, load_in, store_out, scratch):
    """Shared fused-layer loop over one batch tile: for every 128-lane
    group, read its codes with ``load_in(cols)``, run every layer of
    ``metas`` through ``_layer_compute`` (intermediate codes staged in
    ``scratch``), and hand the last layer's output to
    ``store_out(cols, out)``."""
    n_layers = len(metas)

    def body(cols):
        act = load_in(cols)
        for l, (in_bits, sub_bits, use_adder, n_in, n_out, mm,
                sub_field, add_field) in enumerate(metas):
            out = _layer_compute(
                act, refs[1 + 3 * l], refs[2 + 3 * l], refs[3 + 3 * l],
                in_bits=in_bits, sub_bits=sub_bits, use_adder=use_adder,
                matmul_route=mm, sub_field=sub_field, add_field=add_field)
            if l == n_layers - 1:
                store_out(cols, out)
            else:
                scratch[:n_out, cols] = out
                act = scratch[:n_out, cols]

    _for_lane_groups(scratch.shape[1], body)


def _fused_kernel(*refs, metas: Tuple[Tuple[int, int, bool, int, int,
                                            bool, int, int], ...]):
    """refs = [codes, (route, sub, add) * L, out, scratch].

    metas[l] = (in_bits, sub_bits, use_adder, n_in, n_out, matmul_route,
    sub_field, add_field) — static; a field is the bit width of one
    entry of the slab as ``lookup_slab`` laid it out.  route is the
    (n_in, n_out*A) float32 routing matrix when matmul_route else the
    (n_out*A, F) int32 conn.  Inter-layer activation codes are staged
    through the (stage_rows, TB) int32 VMEM scratch; only the input read
    and output write touch HBM.
    """
    n_layers = len(metas)
    codes_ref = refs[0]
    out_ref = refs[1 + 3 * n_layers]
    scratch = refs[2 + 3 * n_layers]

    def store_out(cols, out):
        out_ref[:, cols] = out

    _run_layers(refs, metas, lambda cols: codes_ref[:, cols], store_out,
                scratch)


def _fused_pipelined_kernel(*refs, metas, n_tiles: int):
    """Double-buffered fused kernel: ONE grid step, codes/out refs in
    HBM (``memory_space=ANY``), the batch-tile loop driven in-kernel
    with async DMA.  refs = [codes_hbm, (route, sub, add) * L, out_hbm,
    inbuf(2, slot_rows(n_in), TB), outbuf(2, slot_rows(n_out), TB),
    scratch, insem(2), outsem(2)].

    Tile i's schedule: start tile i+1's HBM->VMEM copy, wait tile i's,
    reclaim this out-slot (wait tile i-2's VMEM->HBM store), compute,
    start tile i's store.  Neighbouring tiles' transfers therefore
    overlap the current tile's compute — the grid-mode path reuses one
    buffer pair serially instead.
    """
    n_layers = len(metas)
    codes_hbm = refs[0]
    out_hbm = refs[1 + 3 * n_layers]
    inbuf, outbuf, scratch, insem, outsem = refs[2 + 3 * n_layers:]
    TB = inbuf.shape[2]

    def in_dma(slot, i):
        return pltpu.make_async_copy(
            codes_hbm.at[:, pl.ds(i * TB, TB)], inbuf.at[slot],
            insem.at[slot])

    def out_dma(slot, i):
        return pltpu.make_async_copy(
            outbuf.at[slot], out_hbm.at[:, pl.ds(i * TB, TB)],
            outsem.at[slot])

    n_in, n_out = metas[0][3], metas[-1][4]

    def compute(slot):
        def store_out(cols, out):
            outbuf[slot, :n_out, cols] = out

        _run_layers(refs, metas, lambda cols: inbuf[slot, :n_in, cols],
                    store_out, scratch)

    in_dma(0, 0).start()

    if n_tiles <= PIPELINE_UNROLL_MAX_TILES:
        # n_tiles is static: unroll with STATIC slot indices — every
        # buffer access is a plain (not dynamic) slice and every
        # schedule branch folds away at trace time
        for i in range(n_tiles):
            slot = i % 2
            if i + 1 < n_tiles:
                in_dma((i + 1) % 2, i + 1).start()
            in_dma(slot, i).wait()
            if i >= 2:             # reclaim: this slot's previous store
                out_dma(slot, i - 2).wait()
            compute(slot)
            out_dma(slot, i).start()
    else:
        # huge tile counts: a rolled loop bounds program size; slot
        # indices become dynamic (traced fori_loop induction variable)
        def step(i, carry):
            slot = i % 2

            @pl.when(i + 1 < n_tiles)
            def _():
                in_dma((i + 1) % 2, i + 1).start()

            in_dma(slot, i).wait()

            @pl.when(i >= 2)
            def _():
                out_dma(slot, i - 2).wait()

            compute(slot)
            out_dma(slot, i).start()
            return carry

        jax.lax.fori_loop(0, n_tiles, step, 0)

    # drain the (up to two) stores still in flight
    if n_tiles >= 2:
        out_dma((n_tiles - 2) % 2, n_tiles - 2).wait()
    out_dma((n_tiles - 1) % 2, n_tiles - 1).wait()


def _kernel_layout(flat_tables, metas):
    """The fused kernel's view of ``ops._flatten_network``'s arrays:
    (n_out, A, ·) conn and sub slabs as (n_out*A, ·) rows."""
    out = []
    for l, meta in enumerate(metas):
        route, sub, add = flat_tables[3 * l:3 * l + 3]
        if not meta[5]:                       # conn, not a routing matrix
            route = route.reshape(-1, route.shape[-1])
        out += [route, sub.reshape(-1, sub.shape[-1]), add]
    return out


@functools.partial(jax.jit, static_argnames=("metas", "block_b",
                                             "interpret", "pipeline"))
def lut_network_fused_pallas(codes: jnp.ndarray,
                             flat_tables: Tuple[jnp.ndarray, ...],
                             metas: Tuple[Tuple[int, int, bool, int, int,
                                                bool, int, int], ...],
                             block_b: int = 256,
                             interpret: bool = False,
                             pipeline: bool = False) -> jnp.ndarray:
    """Run every layer of a synthesised LUT network in one kernel.

    codes: (B, n_in) int32.  flat_tables: (route_l, sub_l, add_l) for
    each layer, concatenated — route_l is the matmul routing matrix or
    the conn array, per metas[l] = (in_bits, sub_bits, use_adder, n_in,
    n_out, matmul_route, sub_field, add_field).  Returns
    (B, n_out_last) int32.  Slabs come laid out by ``lookup_slab`` and
    empty adder tables pre-replaced by ``dummy_add_table``
    (ops._flatten_network does both).
    ``pipeline=True`` switches from the grid-per-tile path to the
    double-buffered in-kernel tile loop (module docstring, "Tile
    pipeline").
    """
    B, n_in = codes.shape
    n_layers = len(metas)
    assert len(flat_tables) == 3 * n_layers
    n_out_last = metas[-1][4]
    rows = stage_rows([m[4] for m in metas])

    TB = batch_tile(block_b, B)
    pad_b = (-B) % TB
    Bp = B + pad_b
    tables = _kernel_layout(flat_tables, metas)
    slab_bytes = sum(_nbytes(t.shape, t.dtype) for t in tables)
    table_specs = [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in tables]

    if pipeline:
        # HBM codes and DMA slots are padded to whole sublane tiles
        r_in, r_out = slot_rows(n_in), slot_rows(n_out_last)
        codes_t = jnp.pad(codes, ((0, pad_b), (0, r_in - n_in))).T
        kernel = functools.partial(_fused_pipelined_kernel, metas=metas,
                                   n_tiles=Bp // TB)
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + table_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((r_out, Bp), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((2, r_in, TB), jnp.int32),        # in slots
                pltpu.VMEM((2, r_out, TB), jnp.int32),       # out slots
                pltpu.VMEM((rows, TB), jnp.int32),           # activations
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            compiler_params=_compiler_params(
                slab_bytes + 4 * TB * (2 * (r_in + r_out) + rows)),
            interpret=interpret,
        )(codes_t, *tables)
        return out[:n_out_last, :B].T

    # batch tile moves through the grid; every table slab is bound once
    # as a whole VMEM-resident array
    codes_t = jnp.pad(codes, ((0, pad_b), (0, 0))).T         # (n_in, Bp)
    kernel = functools.partial(_fused_kernel, metas=metas)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // TB,),
        in_specs=[pl.BlockSpec((n_in, TB), lambda i: (0, i))] + table_specs,
        out_specs=pl.BlockSpec((n_out_last, TB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_out_last, Bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((rows, TB), jnp.int32)],
        # the pipeline double-buffers the in/out blocks
        compiler_params=_compiler_params(
            slab_bytes + 4 * TB * (2 * (n_in + n_out_last) + rows)),
        interpret=interpret,
    )(codes_t, *tables)
    return out[:, :B].T
