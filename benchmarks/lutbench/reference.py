"""Plain reference of a LUT network, in numpy on the host.

The same chain as ``kernels/lut_gather/ref.lut_layer``, written again
here so that the comparison shares no code with the program: gather the
F fan-in codes of each sub-neuron, pack them into a table address
(slot 0 in the low bits), look up the sub-neuron code, pack the A
sub-neuron codes and look up the adder table.  Integer arithmetic
throughout, so the answer is exact and the comparison is exact.

``lower_precision`` gives the control: every table stored one integer
width lower than the configuration states (8-bit codes saturated to 4
bits, the 16-bit logit codes cut to their high 8 bits).
"""
from __future__ import annotations

from typing import List

import numpy as np

ROW_BLOCK = 4096


def _pack(codes: np.ndarray, bits: int) -> np.ndarray:
    """(..., K) codes -> (...) address, slot 0 in the low bits."""
    shifts = bits * np.arange(codes.shape[-1], dtype=np.int64)
    return np.sum(codes.astype(np.int64) << shifts, axis=-1)


def _layer(codes: np.ndarray, layer: dict) -> np.ndarray:
    s = layer["shape"]
    conn, sub, add = layer["conn"], layer["sub"], layer["add"]
    n_out, a = conn.shape[:2]
    addr = _pack(codes[:, conn], s.in_bits)               # (B, n_out, A)
    sub_codes = sub[np.arange(n_out)[:, None], np.arange(a)[None, :], addr]
    if s.adder_width == 1:
        return sub_codes[..., 0].astype(np.int32)
    aaddr = _pack(sub_codes, s.sub_bits)                  # (B, n_out)
    return add[np.arange(n_out)[None, :], aaddr].astype(np.int32)


def forward(layers: List[dict], codes: np.ndarray) -> np.ndarray:
    """(B, n_in) int input codes -> (B, n_out) int32 output codes, in
    blocks of ``ROW_BLOCK`` rows."""
    out = []
    for lo in range(0, codes.shape[0], ROW_BLOCK):
        x = np.asarray(codes[lo:lo + ROW_BLOCK])
        for layer in layers:
            x = _layer(x, layer)
        out.append(x)
    return np.concatenate(out)


def lower_precision(layers: List[dict]) -> List[dict]:
    """The control's tables: each stored one integer width lower than
    served (uint8 codes in 4 bits, saturating; int32 16-bit logit codes
    in 8 bits, keeping the high byte)."""
    def lower(t: np.ndarray) -> np.ndarray:
        if t.dtype == np.uint8:
            return np.minimum(t, 15)
        return (t >> 8) << 8

    return [dict(layer, sub=lower(layer["sub"]), add=lower(layer["add"]))
            for layer in layers]


def mismatched_rows(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of ``got`` that differ from ``want`` anywhere."""
    if got.shape != want.shape:
        return int(want.shape[0])
    return int(np.count_nonzero(np.any(got != want, axis=1)))
