"""Public entry point for LUT-mode inference.

``lut_layer`` runs one synthesised layer; ``lut_network`` runs a whole
synthesised LUT-DNN (list of core/lut_synth.LayerTables) layer by
layer, and ``lut_network_fused`` runs it in a SINGLE pallas_call —
every table slab VMEM-resident, inter-layer codes in VMEM scratch, one
HBM read + one HBM write per forward pass.  int4 nibble-packed slabs
(lut_synth.pack_tables_int4 or a packed artifact load) stay packed in
VMEM and unpack per lookup in-kernel, halving table residency (byte
slabs with rows over 128 bytes are bound as 32-bit words, four bytes a
lane: 8 gathers instead of 32 for a 4,096-byte row);
``pipeline=True`` double-buffers the fused kernel's batch tiles so a
tile's HBM transfers overlap its neighbour's compute; and
``tune_block_b`` sweeps the batch-tile size.  All paths match
core/lut_synth.lut_forward bit-exactly (tested by the cross-engine
conformance harness, tests/test_conformance.py).

Networks whose slabs exceed ``FUSED_VMEM_BUDGET_BYTES`` are no longer
a cliff: ``plan_segments`` partitions the layer list into the fewest
VMEM-sized segments (tie-broken on cut-point width, since the cut
layer's code vector is what rides HBM between segments), preferring
int4-packed slabs when packing pulls a segment under budget, and
``lut_network_segmented`` executes the plan as a chain of fused
pallas_calls — inter-segment codes staged through HBM and
double-buffered by the pipelined kernel's DMA slots.  One segment is
exactly today's fully-fused path; per-layer survives only as the last
resort when a single layer alone cannot fit.

``lut_network_fused_sharded`` scales the fused engine across devices:
shard_map over the batch axis of a data-parallel mesh, every table
slab replicated — LUT-DNN tables are tiny by construction (the
PolyLUT-Add decomposition is what keeps them VMEM-sized), so
replicate-tables/shard-batch is the natural axis and needs ZERO
cross-device communication per forward pass.

Backend detection is hoisted to import-level caching and the Pallas
wrappers are jitted with static config, so repeated ``lut_layer`` /
``lut_network`` calls on stable shapes never retrace.  Routing
matrices are read from the ``LayerTables.routing`` cache that
core/lut_synth now fills at synthesis time — a trace never rebuilds
them.  For serving, ``make_network_fn`` closes over the tables once
and returns a single jitted callable (optionally with donated input
buffers, optionally sharded over a mesh).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.lut_gather.lut_gather import (LANES,
                                                 MATMUL_ROUTE_MAX_BITS,
                                                 batch_tile,
                                                 dummy_add_table,
                                                 lookup_row_chunks,
                                                 lookup_slab,
                                                 lut_gather_pallas,
                                                 lut_network_fused_pallas,
                                                 routing_matrix, slot_rows,
                                                 stage_rows)
from repro.kernels.lut_gather import ref

# VMEM a fused network may claim for tables + activation scratch before
# we refuse to fuse.  Each pallas_call asks the compiler for twice its
# own claim (at least 32 MiB, lut_gather._compiler_params), which
# leaves room for double-buffered tiles, lane padding and temporaries
FUSED_VMEM_BUDGET_BYTES = 12 * 2 ** 20


@functools.lru_cache(maxsize=None)
def _backend() -> str:
    return jax.default_backend()


def _default_interpret(force_interpret: Optional[bool]) -> bool:
    """Compiled kernels on a TPU, the Pallas interpreter on the CPU
    (tests, CPU-only hosts); any other backend is an error rather than
    a silent interpreter run."""
    if force_interpret is not None:
        return force_interpret
    backend = _backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"LUT kernels compile for TPU only (backend {backend!r}); "
            "pass force_interpret=True to run the Pallas interpreter")
    return backend == "cpu"


def lut_layer(codes: jnp.ndarray, conn: jnp.ndarray,
              sub_table: jnp.ndarray, add_table: jnp.ndarray,
              in_bits: int, sub_bits: int,
              force_interpret: Optional[bool] = None,
              sub_packed: bool = False,
              add_packed: bool = False) -> jnp.ndarray:
    return lut_gather_pallas(codes, conn, sub_table, add_table,
                             in_bits=in_bits, sub_bits=sub_bits,
                             interpret=_default_interpret(force_interpret),
                             sub_packed=sub_packed, add_packed=add_packed)


def lut_network(tables: List, codes: jnp.ndarray,
                force_interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-layer path: one pallas_call per layer, codes round-trip
    through HBM between layers.  tables: List[LayerTables]; int4
    nibble-packed slabs run through the in-kernel unpack."""
    for t in tables:
        codes = lut_layer(codes, t.conn, t.sub_table, t.add_table,
                          t.in_bits, t.sub_bits,
                          force_interpret=force_interpret,
                          sub_packed=getattr(t, "sub_packed", False),
                          add_packed=getattr(t, "add_packed", False))
    return codes


def _infer_n_in0(tables: List, n_in0: Optional[int]) -> int:
    """Network input width: as given, else exact from the first layer's
    cached routing matrix, else inferred from the highest conn index
    (which under-counts if connectivity never touches the top input
    features — pass ``n_in0`` when known)."""
    if n_in0 is not None:
        return n_in0
    t0 = tables[0]
    route = getattr(t0, "routing", None)
    if route is not None:
        return route.shape[0]
    try:
        return int(np.asarray(t0.conn).max()) + 1
    except Exception:          # traced conn — conn-size lower bound
        return t0.conn.shape[2]


def _matmul_route(t, n_in: int):
    """Whether layer ``t``, reading ``n_in`` codes, is routed by a
    float32 routing matrix (``codes @ routing_matrix``), and the cached
    matrix to use: ``(matmul_route, cached or None)``.  A cache built
    for another width is ignored; without one, the matrix is derived
    from a concrete conn when the packed address fits
    ``MATMUL_ROUTE_MAX_BITS``."""
    cached = getattr(t, "routing", None)
    if cached is not None and cached.shape[0] != n_in:
        cached = None                        # synthesised for another width
    mm = cached is not None or \
        (t.in_bits * t.conn.shape[2] <= MATMUL_ROUTE_MAX_BITS
         and not isinstance(t.conn, jax.core.Tracer))
    return mm, cached


def _flatten_network(tables: List, n_in0: int):
    """Build the fused kernel's inputs: the flat (route, sub, add) list
    and the static metas tuple — metas[l] = (in_bits, sub_bits,
    use_adder, n_in, n_out, matmul_route, sub_field, add_field).
    Slabs are laid out by ``lookup_slab`` (32-bit words where that cuts
    the lookup's lane chunks; the same bytes), and each field is the bit
    width of one entry there.

    Routing uses the matmul formulation (codes @ routing_matrix) per
    layer whenever the packed address width allows it.  The matrices
    come from the ``LayerTables.routing`` cache filled at synthesis
    time; only hand-built tables without one (or a width mismatch)
    fall back to deriving the matrix from conn at trace time.  Empty
    adder tables are replaced by the zero-width-safe dummy (never
    read, never marked packed).
    """
    flat, metas = [], []
    n_in = n_in0
    for t in tables:
        n_out = t.conn.shape[0]
        use_adder = t.add_table.shape[-1] > 0
        add = (t.add_table if use_adder
               else dummy_add_table(n_out, t.sub_table.dtype))
        mm, cached = _matmul_route(t, n_in)
        route = (cached if cached is not None else
                 routing_matrix(t.conn, t.in_bits, n_in) if mm else t.conn)
        sub, sub_field = lookup_slab(t.sub_table,
                                     getattr(t, "sub_packed", False))
        add, add_field = lookup_slab(
            add, use_adder and getattr(t, "add_packed", False))
        flat.extend([route, sub, add])
        metas.append((t.in_bits, t.sub_bits, use_adder, n_in, n_out, mm,
                      sub_field, add_field))
        n_in = n_out
    return tuple(flat), tuple(metas)


def network_row_chunks(tables: List) -> int:
    """Lane-gather row-chunks per 128-row batch group of one forward
    pass: summed over every sub- and adder-table lookup of ``tables``,
    as the kernels lay the slabs out (``lookup_slab``).  The same on
    every engine path, since each runs every lookup once."""
    return sum(lookup_row_chunks(slab.shape, slab.dtype.itemsize)
               for t in tables for slab in (t.sub_table, t.add_table)
               if slab.shape[-1] > 0)


def network_routing_macs(tables: List, n_in0: int) -> int:
    """Multiply-adds per row of the routing matmuls (``codes @
    routing_matrix``, on the MXU) in one fused forward pass: the sum of
    n_in * n_out * A over the matmul-routed layers of ``tables``.  The
    lookups' work is ``network_row_chunks``."""
    macs, n_in = 0, n_in0
    for t in tables:
        n_out, A, _ = t.conn.shape
        if _matmul_route(t, n_in)[0]:
            macs += n_in * n_out * A
        n_in = n_out
    return macs


def _tile_bytes(n_in0: int, widths: List[int], block_b: int,
                pipeline: bool) -> int:
    """int32 batch-tile + activation-scratch bytes of the fused kernel
    at its batch tile TB (``block_b`` in whole 128-lane groups).  Grid
    mode holds one (n_in, TB) in block and one (n_out_last, TB) out
    block; the double-buffered pipeline holds TWO of each (its DMA
    slots, in whole 8-row sublane tiles).  Both stage hidden
    activations through one (stage_rows, TB) scratch."""
    io = (2 * (slot_rows(n_in0) + slot_rows(widths[-1])) if pipeline
          else n_in0 + widths[-1])
    return batch_tile(block_b) * 4 * (io + stage_rows(widths))


def fused_vmem_bytes(tables: List, block_b: int = 1024,
                     n_in0: Optional[int] = None,
                     pipeline: bool = False) -> int:
    """Estimated VMEM claim of the fused kernel: every table slab AT
    ITS STORED WIDTH (int4 nibble-packed slabs count half), per-layer
    routing (float32 matrix when matmul routing applies, int32 conn
    otherwise, 1-entry dummy for adder-off layers), plus the int32
    batch tiles and activation scratch of ``_tile_bytes``.

    This analytic estimate is pinned against the ACTUAL flattened
    allocation (``fused_vmem_actual``) by tests/test_conformance.py, so
    it cannot silently drift from what the kernel binds."""
    slab = 0
    n_in = _infer_n_in0(tables, n_in0)
    n_in0 = n_in
    for t in tables:
        n_out, A, fan_in = t.conn.shape
        mm, _ = _matmul_route(t, n_in)
        slab += (4 * n_in * n_out * A if mm
                 else 4 * n_out * A * fan_in)                 # route/conn
        slab += int(t.sub_table.size * t.sub_table.dtype.itemsize)
        use_adder = t.add_table.shape[-1] > 0
        slab += (int(t.add_table.size * t.add_table.dtype.itemsize)
                 if use_adder
                 else n_out * t.sub_table.dtype.itemsize)     # dummy
        n_in = n_out
    widths = [t.conn.shape[0] for t in tables]
    return slab + _tile_bytes(n_in0, widths, block_b, pipeline)


def fused_vmem_actual(tables: List, block_b: int = 1024,
                      n_in0: Optional[int] = None,
                      pipeline: bool = False) -> int:
    """MEASURED VMEM claim: the summed bytes of the exact arrays
    ``lut_network_fused`` hands to the kernel (flattened routes, slabs,
    dummies) plus the buffer shapes ``lut_network_fused_pallas``
    allocates — mirrored HERE independently of the ``_tile_bytes``
    estimate term, so the estimator property test compares two separate
    derivations.  The oracle ``fused_vmem_bytes`` is tested against."""
    n_in = _infer_n_in0(tables, n_in0)
    flat, metas = _flatten_network(tables, n_in)
    slab = sum(int(a.size) * a.dtype.itemsize for a in flat)
    # mirror of lut_network_fused_pallas's in/out specs + scratch_shapes
    n_out_last = metas[-1][4]
    rows = stage_rows([m[4] for m in metas])
    itemsize = jnp.dtype(jnp.int32).itemsize
    tb = batch_tile(block_b)
    if pipeline:
        tiles = itemsize * (2 * slot_rows(n_in) * tb    # inbuf slots
                            + 2 * slot_rows(n_out_last) * tb  # outbuf
                            + rows * tb)                # activations
    else:
        tiles = itemsize * (n_in * tb                   # in block
                            + n_out_last * tb           # out block
                            + rows * tb)                # activations
    return slab + tiles


def fused_tile_bytes(tables: List, block_b: int = 1024,
                     n_in0: Optional[int] = None,
                     pipeline: bool = False) -> int:
    """VMEM-per-tile: just the batch-tile + activation-scratch term of
    ``fused_vmem_bytes`` (the part that scales with ``block_b``)."""
    n_in = _infer_n_in0(tables, n_in0)
    return _tile_bytes(n_in, [t.conn.shape[0] for t in tables],
                       block_b, pipeline)


def can_fuse(tables: List, block_b: int = 1024,
             n_in0: Optional[int] = None,
             pipeline: bool = False,
             budget: Optional[int] = None) -> bool:
    if budget is None:
        budget = FUSED_VMEM_BUDGET_BYTES
    return fused_vmem_bytes(tables, block_b, n_in0, pipeline) <= budget


def lut_network_fused(tables: List, codes: jnp.ndarray,
                      block_b: int = 1024,
                      force_interpret: Optional[bool] = None,
                      pipeline: bool = False) -> jnp.ndarray:
    """Fused path: the whole network in one pallas_call.  Requires the
    table slabs to fit the VMEM budget (see ``can_fuse``).  int4
    nibble-packed slabs (``LayerTables.sub_packed``/``add_packed``,
    from ``lut_synth.pack_tables_int4`` or a packed artifact load) stay
    packed in VMEM and unpack per lookup in-kernel.  ``pipeline=True``
    double-buffers the batch tiles' HBM transfers against compute.
    """
    flat, metas = _flatten_network(tables, codes.shape[1])
    return lut_network_fused_pallas(
        codes, flat, metas, block_b=block_b,
        interpret=_default_interpret(force_interpret), pipeline=pipeline)


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """Cost-model-driven execution plan for one synthesised network.

    ``mode`` is ``"fused"`` (one segment — exactly the classic fully
    fused path), ``"segmented"`` (a chain of fused pallas_calls with
    inter-segment codes staged through HBM) or ``"per_layer"`` (last
    resort: some single layer exceeds the budget even at a 128-row
    batch tile).
    ``bounds`` are half-open ``(start, end)`` layer ranges; ``block_b``
    and ``vmem_bytes`` are the per-segment batch tile and VMEM ledger
    at that tile; ``cut_widths`` are the code widths crossing each
    inter-segment cut (each cut moves ``2 * B * width * 4`` HBM bytes
    per forward pass: one store by segment i, one load by i+1).
    ``pack_int4`` records that the planner chose nibble-packed slabs to
    pull segments under budget — the executor applies the packing.
    Plans serialise losslessly through ``summary()``/``from_summary``
    so the artifact manifest can ship them with the model."""
    mode: str
    bounds: Tuple[Tuple[int, int], ...]
    block_b: Tuple[int, ...]
    vmem_bytes: Tuple[int, ...]
    cut_widths: Tuple[int, ...]
    seg_widths: Tuple[Tuple[int, int], ...]   # (n_in, n_out) per segment
    n_in0: int
    budget: int
    pipeline: bool
    pack_int4: bool = False

    @property
    def n_segments(self) -> int:
        return len(self.bounds)

    def hbm_bytes_per_cut(self, batch: int) -> Tuple[int, ...]:
        """HBM bytes each inter-segment cut moves per forward pass of
        ``batch`` rows (int32 codes, written once + read once)."""
        return tuple(2 * 4 * batch * w for w in self.cut_widths)

    def summary(self) -> dict:
        """Plain-JSON summary: what ``serve --lut`` logs and what the
        artifact manifest persists (round-trips via ``from_summary``)."""
        return {
            "mode": self.mode,
            "n_segments": self.n_segments,
            "n_in0": self.n_in0,
            "budget_bytes": self.budget,
            "pipeline": self.pipeline,
            "pack_int4": self.pack_int4,
            "block_b_tuned": list(self.block_b),
            "cut_widths": list(self.cut_widths),
            "segments": [
                {"layers": [s, e], "block_b": bb,
                 "vmem_bytes": int(v), "n_in": wi, "n_out": wo}
                for (s, e), bb, v, (wi, wo) in zip(
                    self.bounds, self.block_b, self.vmem_bytes,
                    self.seg_widths)],
        }

    @classmethod
    def from_summary(cls, d: dict) -> "SegmentPlan":
        segs = d.get("segments", [])
        return cls(
            mode=d["mode"],
            bounds=tuple((int(s["layers"][0]), int(s["layers"][1]))
                         for s in segs),
            block_b=tuple(int(s["block_b"]) for s in segs),
            vmem_bytes=tuple(int(s["vmem_bytes"]) for s in segs),
            cut_widths=tuple(int(w) for w in d.get("cut_widths", [])),
            seg_widths=tuple((int(s["n_in"]), int(s["n_out"]))
                             for s in segs),
            n_in0=int(d["n_in0"]), budget=int(d["budget_bytes"]),
            pipeline=bool(d["pipeline"]),
            pack_int4=bool(d.get("pack_int4", False)))

    def describe(self) -> str:
        """One-line human summary for model-load logging."""
        mb = lambda b: f"{b / 2 ** 20:.2f}MiB"  # noqa: E731
        if self.mode == "per_layer":
            return (f"plan: per-layer fallback (a single layer exceeds "
                    f"the {mb(self.budget)} fused VMEM budget at "
                    f"block_b={LANES})")
        segs = " ".join(
            f"[L{s}..L{e - 1} block_b={bb} vmem={mb(v)}]"
            for (s, e), bb, v in zip(self.bounds, self.block_b,
                                     self.vmem_bytes))
        extra = ""
        if self.mode == "segmented":
            extra = (f" cuts={list(self.cut_widths)}"
                     f" pipeline={self.pipeline}")
        if self.pack_int4:
            extra += " int4-packed"
        return (f"plan: {self.mode} x{self.n_segments} "
                f"(budget {mb(self.budget)}){extra} {segs}")


def _plan_bounds(tables: List, block_b: int, n_in0: int, pipeline: bool,
                 budget: int):
    """Minimum-segment partition of ``tables`` subject to
    ``fused_vmem_bytes(segment) <= budget``, tie-broken on total
    cut-point width (the cut layer's code vector is what crosses HBM).
    Small DP over layer count — L is tens at most, and the vmem of
    every (i, j) range is memoised.  Returns ``(bounds, vmem, cuts,
    seg_widths)`` or None when no feasible cover exists (some single
    layer alone busts the budget)."""
    L = len(tables)
    widths = [t.conn.shape[0] for t in tables]

    def seg_in(i: int) -> int:
        return n_in0 if i == 0 else widths[i - 1]

    vmem_cache = {}

    def seg_vmem(i: int, j: int) -> int:
        if (i, j) not in vmem_cache:
            vmem_cache[(i, j)] = fused_vmem_bytes(
                tables[i:j], block_b, seg_in(i), pipeline)
        return vmem_cache[(i, j)]

    INF = (float("inf"), float("inf"), -1)
    # best[i] = (segments, total cut width, next boundary) for layers i..L
    best = [INF] * L + [(0, 0, L)]
    for i in range(L - 1, -1, -1):
        for j in range(i + 1, L + 1):
            if seg_vmem(i, j) > budget:
                continue
            segs, cutw, _ = best[j]
            cand = (1 + segs, cutw + (widths[j - 1] if j < L else 0), j)
            if cand[:2] < best[i][:2]:
                best[i] = cand
    if best[0][2] < 0:
        return None
    bounds, i = [], 0
    while i < L:
        j = best[i][2]
        bounds.append((i, j))
        i = j
    return (tuple(bounds),
            tuple(seg_vmem(s, e) for s, e in bounds),
            tuple(widths[e - 1] for _, e in bounds[:-1]),
            tuple((seg_in(s), widths[e - 1]) for s, e in bounds))


def plan_segments(tables: List, block_b: int = 1024,
                  n_in0: Optional[int] = None,
                  pipeline: bool = False,
                  budget: Optional[int] = None,
                  prefer_int4: bool = True) -> SegmentPlan:
    """Partition a synthesised network into the fewest VMEM-sized fused
    segments.  Degrades gracefully: a network that fits the budget
    plans to exactly ONE segment (mode ``"fused"`` — byte-identical to
    the classic fully fused path); an oversized network plans to N
    fused segments with inter-segment codes staged through HBM.

    A single layer that alone busts the budget at ``block_b`` (a
    3,072-wide first layer's routing matrix and input tile) makes the
    planner try again at half the batch tile, in whole 128-row groups,
    down to 128; the plan records the tile it chose per segment.  Only
    a layer that busts the budget at a 128-row tile falls back to mode
    ``"per_layer"``.  A network that plans at ``block_b`` gets exactly
    that plan.

    Multi-segment plans run each segment through the double-buffered
    pipelined kernel (codes already live in HBM between segments, which
    is exactly the layout ``pipeline=True`` stages via its DMA slots) —
    unless that larger tile claim would cost an extra cut, in which
    case the grid-mode segments stand.  With ``prefer_int4`` the
    planner also tries nibble-packing eligible slabs and adopts the
    packing when it reduces the segment count (or rescues a plan
    entirely); ``pack_int4`` on the returned plan tells the executor
    to apply it."""
    if budget is None:
        budget = FUSED_VMEM_BUDGET_BYTES
    if hasattr(tables, "tables"):          # repro.artifact.Artifact
        if n_in0 is None:
            n_in0 = getattr(tables, "n_in", None)
        tables = tables.tables
    tables = list(tables)
    n_in0 = _infer_n_in0(tables, n_in0)

    def build(tbls, tile):
        pipe = pipeline
        r = _plan_bounds(tbls, tile, n_in0, pipe, budget)
        if r is None:
            return None
        if len(r[0]) > 1 and not pipe:
            r2 = _plan_bounds(tbls, tile, n_in0, True, budget)
            if r2 is not None and len(r2[0]) == len(r[0]):
                r, pipe = r2, True
        return r, pipe

    packed4 = None
    if prefer_int4 and not any(getattr(t, "sub_packed", False) or
                               getattr(t, "add_packed", False)
                               for t in tables):
        from repro.core.lut_synth import pack_tables_int4
        packed4 = pack_tables_int4(tables)
        if not any(t.sub_packed or t.add_packed for t in packed4):
            packed4 = None

    tile = block_b
    while True:
        chosen, pack_int4 = build(tables, tile), False
        if packed4 is not None:
            alt = build(packed4, tile)
            if alt is not None and (chosen is None or
                                    len(alt[0][0]) < len(chosen[0][0])):
                chosen, pack_int4 = alt, True
        if chosen is not None or batch_tile(tile) <= LANES:
            break
        tile = max(LANES, batch_tile(tile) // (2 * LANES) * LANES)

    if chosen is None:
        return SegmentPlan(mode="per_layer", bounds=(), block_b=(),
                           vmem_bytes=(), cut_widths=(), seg_widths=(),
                           n_in0=n_in0, budget=budget, pipeline=False,
                           pack_int4=False)
    (bounds, vmem, cuts, segw), pipe = chosen
    mode = "fused" if len(bounds) == 1 else "segmented"
    return SegmentPlan(mode=mode, bounds=bounds,
                       block_b=(tile,) * len(bounds), vmem_bytes=vmem,
                       cut_widths=cuts, seg_widths=segw, n_in0=n_in0,
                       budget=budget, pipeline=pipe, pack_int4=pack_int4)


def _apply_plan_packing(tables: List, plan: SegmentPlan) -> List:
    """Materialise the plan's int4 preference (no-op when the tables
    already carry packed slabs, e.g. a packed artifact load)."""
    if plan.pack_int4 and not any(getattr(t, "sub_packed", False) or
                                  getattr(t, "add_packed", False)
                                  for t in tables):
        from repro.core.lut_synth import pack_tables_int4
        tables = pack_tables_int4(tables)
    return tables


def _execute_plan(tables: List, codes: jnp.ndarray, plan: SegmentPlan,
                  force_interpret: Optional[bool]) -> jnp.ndarray:
    """Run a ``SegmentPlan``: per-layer fallback, or the segment chain
    (one fused pallas_call per segment — a single segment IS the
    classic fused path).  Between segments the code tensor is an
    ordinary jax array, i.e. HBM-resident; ``plan.pipeline`` makes each
    segment's kernel double-buffer its tile DMAs against compute, so
    segment boundaries add no VMEM residency — just the cut layer's
    codes riding HBM once."""
    if plan.mode == "per_layer":
        return lut_network(tables, codes, force_interpret=force_interpret)
    for (s, e), bb in zip(plan.bounds, plan.block_b):
        codes = lut_network_fused(tables[s:e], codes, block_b=bb,
                                  force_interpret=force_interpret,
                                  pipeline=plan.pipeline)
    return codes


def lut_network_segmented(tables: List, codes: jnp.ndarray,
                          plan: Optional[SegmentPlan] = None,
                          block_b: int = 1024,
                          n_in0: Optional[int] = None,
                          force_interpret: Optional[bool] = None,
                          budget: Optional[int] = None) -> jnp.ndarray:
    """Segmented fused inference: plan (or take a precomputed plan) and
    execute the chain of VMEM-sized fused segments.  Bit-exact against
    ``lut_network`` and the jnp oracle on every mode the planner can
    choose (pinned by tests/test_conformance.py)."""
    if plan is None:
        plan = plan_segments(tables, block_b=block_b,
                             n_in0=n_in0 if n_in0 is not None
                             else codes.shape[1],
                             budget=budget)
    tables = _apply_plan_packing(list(tables), plan)
    return _execute_plan(tables, codes, plan, force_interpret)


def _mesh_batch_shards(mesh: Mesh) -> int:
    """Number of batch shards a serving mesh yields: the product of its
    data-parallel axes (every axis except `model`)."""
    return int(np.prod([s for a, s in mesh.shape.items() if a != "model"],
                       initial=1))


def _mesh_batch_spec(mesh: Mesh) -> P:
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return P(axes if len(axes) > 1 else axes[0], None)


def lut_network_fused_sharded(tables: List, codes: jnp.ndarray,
                              mesh: Mesh, block_b: int = 1024,
                              force_interpret: Optional[bool] = None,
                              fused: bool = True,
                              pipeline: bool = False,
                              plan: Optional[SegmentPlan] = None
                              ) -> jnp.ndarray:
    """Data-parallel fused inference: batch sharded over the mesh's DP
    axes via shard_map, table slabs replicated (closed over — they are
    tiny by construction, so replication is free relative to moving
    activations).  Each device runs the single-kernel fused engine on
    its local batch shard; there is NO cross-device communication.

    Uneven batches are padded up to a multiple of the shard count and
    sliced back, so any B works on any device count — bit-exactness
    against the single-device oracle is property-tested across device
    counts in tests/test_lut_sharded.py.

    A ``plan`` overrides the binary ``fused`` switch: each device runs
    the plan's segment chain on its local batch shard (the tables are
    replicated whole — segmentation bounds VMEM per kernel, not the
    replicated HBM copy, so the sharding story is unchanged).
    """
    n_shards = _mesh_batch_shards(mesh)
    B = codes.shape[0]
    pad = (-B) % n_shards
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))

    if plan is not None:
        tables = _apply_plan_packing(list(tables), plan)

        def local(c):
            return _execute_plan(tables, c, plan, force_interpret)
    elif fused:
        def local(c):
            return lut_network_fused(tables, c, block_b=block_b,
                                     force_interpret=force_interpret,
                                     pipeline=pipeline)
    else:
        def local(c):
            return lut_network(tables, c, force_interpret=force_interpret)

    spec = _mesh_batch_spec(mesh)
    out = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                        check_vma=False)(codes)
    return out[:B]


def tune_block_b(tables: List, batch: int = 2048,
                 candidates=(128, 256, 512, 1024, 2048),
                 iters: int = 3, n_in0: Optional[int] = None,
                 force_interpret: Optional[bool] = None,
                 pipeline: bool = False,
                 budget: Optional[int] = None):
    """Sweep the fused kernel's batch-tile size and return
    ``(best_block_b, {block_b: seconds})``.

    Candidates are clamped to the probe batch and filtered to those
    whose tile+scratch claim still fits the VMEM budget; each survivor
    is timed over ``iters`` synchronous runs on random codes (after one
    warm-up/compile call).  The CPU interpret proxy picks a tile as
    readily as real hardware does — only the winner differs — so the
    sweep is cheap enough to run at serving-process start via
    ``make_network_fn(block_b="auto")``.
    """
    import time as _time

    n_in = _infer_n_in0(tables, n_in0)
    cand = sorted({min(c, batch) for c in candidates})
    cand = [c for c in cand if can_fuse(tables, c, n_in, pipeline, budget)]
    if not cand:
        # never time a config already known not to fit — on real TPU
        # that probe can OOM the serving process at startup
        raise ValueError(
            "no block_b candidate fits the fused VMEM budget for this "
            "network — serve it through the per-layer engine "
            "(make_network_fn(fused=False))")
    codes = jax.random.randint(jax.random.key(0), (batch, n_in), 0,
                               2 ** tables[0].in_bits).astype(jnp.int32)
    timings = {}
    for bb in cand:
        fn = jax.jit(functools.partial(
            lut_network_fused, tables, block_b=bb,
            force_interpret=force_interpret, pipeline=pipeline))
        jax.block_until_ready(fn(codes))             # compile + warm
        t0 = _time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(codes))
        timings[bb] = (_time.perf_counter() - t0) / iters
    best = min(timings, key=timings.get)
    return best, timings


def _tune_plan(tables: List, plan: SegmentPlan, tune_batch: int,
               force_interpret: Optional[bool]) -> SegmentPlan:
    """Per-segment ``tune_block_b`` sweep: each segment gets its own
    winning tile (a narrow tail segment tolerates a far larger tile
    than a wide head segment).  Candidates are budget-filtered per
    segment, so tuning can never push a planned segment over the
    budget the planner admitted it under."""
    widths = [t.conn.shape[0] for t in tables]
    tuned, vmem = [], []
    for s, e in plan.bounds:
        seg_in = plan.n_in0 if s == 0 else widths[s - 1]
        bb, _ = tune_block_b(tables[s:e], batch=tune_batch,
                             n_in0=seg_in,
                             force_interpret=force_interpret,
                             pipeline=plan.pipeline, budget=plan.budget)
        tuned.append(bb)
        vmem.append(fused_vmem_bytes(tables[s:e], bb, seg_in,
                                     plan.pipeline))
    return dataclasses.replace(plan, block_b=tuple(tuned),
                               vmem_bytes=tuple(vmem))


def make_network_fn(tables: List, fused: Optional[bool] = None,
                    block_b=1024,
                    force_interpret: Optional[bool] = None,
                    donate: bool = False,
                    n_in0: Optional[int] = None,
                    mesh: Optional[Mesh] = None,
                    pipeline: bool = False,
                    tune_batch: int = 2048,
                    plan=None,
                    budget: Optional[int] = None) -> Callable:
    """Close over a synthesised network once and return one jitted
    ``fn(codes) -> out_codes`` for serving.  ``fused=None`` (the
    default) drives the engine choice through ``plan_segments``: one
    fused kernel when the tables fit VMEM, a chain of fused segments
    when they do not, a smaller batch tile than ``block_b`` when a
    single layer busts the budget at ``block_b``, per-layer only when
    one busts it at a 128-row tile — pass ``n_in0`` (the network input
    width) for an exact first-layer routing-matrix estimate in that
    decision.  ``fused=True``/``False`` force the classic
    whole-network-fused / per-layer engines.  The decision is
    observable: the returned callable carries the chosen plan as
    ``fn.execution_plan`` (mode, segment bounds, per-segment VMEM
    ledger and block_b — ``fn.execution_plan.describe()`` is the
    one-liner ``serve --lut`` logs at model load), and the work mix of
    one forward pass as ``fn.lookup_row_chunks`` (lane-gather
    row-chunks per 128-row group, ``network_row_chunks``) and
    ``fn.routing_macs_per_row`` (routing-matmul multiply-adds per row,
    ``network_routing_macs``; 0 on the per-layer engine, which routes
    by conn).

    ``block_b="auto"`` runs the ``tune_block_b`` sweep (probing at
    ``tune_batch``) PER SEGMENT before closing over the winners.
    ``pipeline=True`` selects the double-buffered fused kernel (forced
    on for multi-segment plans unless it would cost an extra cut).
    ``donate=True`` donates the input codes buffer on EVERY path —
    single-device and sharded alike (the serving loop builds a fresh
    device array per microbatch and never reads the codes again): the
    argument is marked a buffer donor (``jax.buffer_donor`` in the
    lowering) so the runtime may reuse its memory for the
    padded/sharded staging copies; a donated array must not be passed
    twice.  ``mesh`` switches to the shard_map data-parallel path:
    batch sharded over the mesh, tables replicated, each device running
    the plan's segment chain on its shard.

    ``tables`` may also be a loaded ``repro.artifact`` bundle (anything
    with ``.tables``): the table list is unwrapped, the manifest's
    recorded input width feeds the planner — including a PACKED load
    (``load_artifact(..., unpack_int4=False)``), whose int4 slabs flow
    through the fused and sharded engines unexpanded — and a persisted
    ``execution_plan`` in the manifest is adopted as-is, skipping BOTH
    the planner and the ``tune_block_b`` sweep on cold load (the plan
    ships ``block_b_tuned`` per segment).  An explicit ``plan``
    argument (a ``SegmentPlan`` or its ``summary()`` dict) wins over
    everything else.
    """
    saved_plan = None
    if hasattr(tables, "tables"):          # repro.artifact.Artifact
        if n_in0 is None:
            n_in0 = getattr(tables, "n_in", None)
        saved_plan = getattr(tables, "execution_plan", None)
        tables = tables.tables
    tables = list(tables)
    if isinstance(plan, dict):
        plan = SegmentPlan.from_summary(plan)
    if plan is None and fused is None and saved_plan:
        plan = SegmentPlan.from_summary(saved_plan)

    planned_here = False
    if plan is None:
        if fused is True:
            # forced whole-network fusion: no budget gate, exactly the
            # classic path (block_b="auto" still sweeps the tile)
            if block_b == "auto":
                probe = (max(1, tune_batch // _mesh_batch_shards(mesh))
                         if mesh is not None else tune_batch)
                block_b, _ = tune_block_b(tables, batch=probe,
                                          n_in0=n_in0,
                                          force_interpret=force_interpret,
                                          pipeline=pipeline)
            n_in = _infer_n_in0(tables, n_in0)
            widths = [t.conn.shape[0] for t in tables]
            plan = SegmentPlan(
                mode="fused", bounds=((0, len(tables)),),
                block_b=(block_b,),
                vmem_bytes=(fused_vmem_bytes(tables, block_b, n_in,
                                             pipeline),),
                cut_widths=(), seg_widths=((n_in, widths[-1]),),
                n_in0=n_in,
                budget=(FUSED_VMEM_BUDGET_BYTES if budget is None
                        else budget),
                pipeline=pipeline, pack_int4=False)
        elif fused is False:
            plan = SegmentPlan(
                mode="per_layer", bounds=(), block_b=(), vmem_bytes=(),
                cut_widths=(), seg_widths=(),
                n_in0=_infer_n_in0(tables, n_in0),
                budget=(FUSED_VMEM_BUDGET_BYTES if budget is None
                        else budget),
                pipeline=False, pack_int4=False)
        else:
            # plan at the smallest plausible tile when tuning follows —
            # that minimises the segment count; the per-segment sweep
            # then grows each tile as far as the budget allows
            probe_bb = 128 if block_b == "auto" else block_b
            plan = plan_segments(tables, block_b=probe_bb, n_in0=n_in0,
                                 pipeline=pipeline, budget=budget)
            planned_here = True

    tables = _apply_plan_packing(tables, plan)

    if block_b == "auto" and planned_here and plan.mode != "per_layer":
        # under a mesh each device sees only its batch shard, so the
        # sweep must probe at the PER-SHARD batch — a winner tuned on
        # the global batch would be clamped (TB=min) to a tile size
        # that never ran
        probe = (max(1, tune_batch // _mesh_batch_shards(mesh))
                 if mesh is not None else tune_batch)
        plan = _tune_plan(tables, plan, probe, force_interpret)

    eff_plan = plan
    if mesh is not None:
        def fn(codes):
            return lut_network_fused_sharded(
                tables, codes, mesh,
                force_interpret=force_interpret, plan=eff_plan)
    else:
        def fn(codes):
            return _execute_plan(tables, codes, eff_plan, force_interpret)

    # donation used to be TPU-gated (old CPU runtimes warned and
    # dropped it); current jax accepts buffer donors on every backend,
    # and the sharded path in particular wants the input freed for its
    # padded per-shard staging copies — so apply it wherever asked
    jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
    jitted.execution_plan = eff_plan
    jitted.lookup_row_chunks = network_row_chunks(tables)
    jitted.routing_macs_per_row = (
        0 if plan.mode == "per_layer" else
        network_routing_macs(tables, plan.n_in0))
    return jitted


lut_layer_reference = ref.lut_layer
