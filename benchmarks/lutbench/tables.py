"""Seeded LUT networks: layer shapes from a configuration file, random
truth tables drawn on the host from the seed, and the artifact the
served path cold-loads.

A synthesised LUT network is, per layer, a connectivity array
``conn (n_out, A, F)``, sub-neuron tables ``sub (n_out, A, 2**(b_in*F))``
and adder tables ``add (n_out, 2**(A*sub_bits))``.  Lookup cost does not
depend on the table values, so the benchmark draws them at random from
the seed, in the dtype they are served in (uint8 codes, int32 for the
16-bit logit codes of the output layer).  They are at most a few MB and
the reference reads them on the host, so numpy draws them there in
milliseconds; a jitted draw on the device cost seconds of set-up
(tracing, loading the program, the first execution).  The plain
reference (``reference.py``) gets these same host arrays; the program
gets them only through ``save_artifact`` / ``load_artifact``.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import numpy as np


class LayerShape(NamedTuple):
    n_in: int
    n_out: int
    fan_in: int        # F
    adder_width: int   # A
    in_bits: int       # b_in: width of the codes this layer reads
    sub_bits: int      # width of the sub-table codes
    out_bits: int      # width of the codes this layer emits

    @property
    def sub_entries(self) -> int:
        return 2 ** (self.in_bits * self.fan_in)

    @property
    def add_entries(self) -> int:
        return 2 ** (self.adder_width * self.sub_bits) \
            if self.adder_width > 1 else 0


def layer_shapes(cfg: dict) -> Tuple[LayerShape, ...]:
    """Per-layer shapes of a configuration file, from its numbers alone:
    the first layer reads ``input_bits``-wide codes with fan-in
    ``input_fan_in``; an adder (A > 1) is fed ``bits + 1``-wide
    sub-neuron codes; the output layer emits ``logit_bits``-wide codes."""
    dims = [cfg["in_features"]] + list(cfg["widths"])
    n_layers = len(cfg["widths"])
    out = []
    for i in range(n_layers):
        first, last = i == 0, i == n_layers - 1
        b_in = cfg["input_bits"] if first else cfg["bits"]
        f = cfg["input_fan_in"] if first else cfg["fan_in"]
        b_out = cfg["logit_bits"] if last else cfg["bits"]
        a = cfg["adder_width"]
        out.append(LayerShape(
            n_in=dims[i], n_out=dims[i + 1], fan_in=min(f, dims[i]),
            adder_width=a, in_bits=b_in,
            sub_bits=cfg["bits"] + 1 if a > 1 else b_out, out_bits=b_out))
    return tuple(out)


def code_dtype(bits: int):
    """The dtype a table of ``bits``-wide codes is served in."""
    return np.uint8 if bits <= 8 else np.int32


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of ``seed`` (tables, inputs,
    traffic)."""
    return np.random.default_rng([seed, stream])


def draw_tables(cfg: dict, seed: int) -> List[dict]:
    """Random tables of ``cfg`` from ``seed``, as host arrays: one dict
    per layer with ``conn``, ``sub``, ``add`` and the layer's ``shape``."""
    out = []
    for i, s in enumerate(layer_shapes(cfg)):
        rng = host_rng(seed, 100 + i)
        k = s.adder_width * s.fan_in
        if k <= s.n_in:       # distinct inputs per neuron, as random_conn
            conn = np.argsort(rng.random((s.n_out, s.n_in)), axis=1)[:, :k]
        else:
            conn = rng.integers(0, s.n_in, (s.n_out, k))
        conn = conn.reshape(s.n_out, s.adder_width, s.fan_in)
        sub = rng.integers(0, 2 ** s.sub_bits,
                           (s.n_out, s.adder_width, s.sub_entries))
        add = rng.integers(0, 2 ** s.out_bits, (s.n_out, s.add_entries))
        out.append({"shape": s, "conn": conn.astype(np.int32),
                    "sub": sub.astype(code_dtype(s.sub_bits)),
                    "add": add.astype(code_dtype(s.out_bits))})
    return out


def program_tables(cfg: dict, layers: List[dict]):
    """The program's ``LayerTables`` for ``layers``, with the quantiser
    metadata and routing matrices the program's synthesis would attach."""
    import jax.numpy as jnp

    from repro.core.lut_synth import LayerTables
    from repro.kernels.lut_gather.lut_gather import (MATMUL_ROUTE_MAX_BITS,
                                                     routing_matrix)

    specs = model_spec(cfg).layer_specs()
    out = []
    for ls, layer in zip(specs, layers):
        s = layer["shape"]
        if (ls.n_in, ls.n_out, ls.fan_in, ls.in_quant.bits) != (
                s.n_in, s.n_out, s.fan_in, s.in_bits):
            raise ValueError(f"layer shape {s} disagrees with the "
                             f"program's layer spec {ls}")
        route = (routing_matrix(layer["conn"], s.in_bits, s.n_in)
                 if s.in_bits * s.fan_in <= MATMUL_ROUTE_MAX_BITS else None)
        out.append(LayerTables(
            conn=jnp.asarray(layer["conn"]),
            sub_table=jnp.asarray(layer["sub"]),
            add_table=jnp.asarray(layer["add"]),
            in_bits=s.in_bits,
            sub_bits=s.sub_bits if s.adder_width > 1 else cfg["bits"],
            out_bits=cfg["bits"],
            fan_in=s.fan_in, adder_width=s.adder_width,
            is_output=ls.is_output, out_quant=ls.out_quant,
            sub_quant=ls.sub_quant,
            table_dtype=jnp.dtype(layer["sub"].dtype), routing=route))
    return out


def model_spec(cfg: dict):
    from repro.core.lutdnn import ModelSpec

    return ModelSpec(name=cfg["model"], in_features=cfg["in_features"],
                     widths=tuple(cfg["widths"]), bits=cfg["bits"],
                     fan_in=cfg["fan_in"], degree=cfg["degree"],
                     adder_width=cfg["adder_width"],
                     input_bits=cfg["input_bits"],
                     input_fan_in=cfg["input_fan_in"])


def save(cfg: dict, layers: List[dict], out_dir: str) -> str:
    """Save ``layers`` as a program artifact, with the execution plan the
    program's planner picks, and return its directory."""
    from repro.artifact import save_artifact
    from repro.kernels.lut_gather import ops

    tables = program_tables(cfg, layers)
    plan = ops.plan_segments(tables, n_in0=cfg["in_features"])
    return save_artifact(out_dir, tables, name=cfg["name"],
                         spec=model_spec(cfg), plan=plan)


def input_codes(cfg: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` rows of input codes, uniform over [0, 2**input_bits)."""
    return rng.integers(0, 2 ** cfg["input_bits"],
                        (n, cfg["in_features"]), dtype=np.int32)

