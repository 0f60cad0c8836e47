"""The work one engine call requires, counted from a configuration's
shapes alone, and the least time a chip could take for it.

The count stays the same whatever implements the network:

* operations per row: for each neuron, A sub-table lookups, each with
  its F address shift-adds, plus one adder-table lookup with its A
  shift-adds (a neuron without adder: its one lookup and F shift-adds);
* bytes per call: every table at one byte per entry, read once per
  call, plus one byte per input code and per output code of each row.

The least time is the larger of operations over the chip's int8 peak
and bytes over its HBM bandwidth (``peaks.json``, keyed by
``device_kind``; a chip that is not there is an error).
"""
from __future__ import annotations

import json
import os
from typing import Dict

from tables import layer_shapes

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def ops_per_row(cfg: dict) -> int:
    total = 0
    for s in layer_shapes(cfg):
        per_neuron = s.adder_width * (1 + s.fan_in)
        if s.adder_width > 1:
            per_neuron += 1 + s.adder_width
        total += s.n_out * per_neuron
    return total


def table_entries(cfg: dict) -> int:
    return sum(s.n_out * (s.adder_width * s.sub_entries + s.add_entries)
               for s in layer_shapes(cfg))


def call_bytes(cfg: dict, rows: int) -> int:
    return table_entries(cfg) + rows * (cfg["in_features"] + cfg["widths"][-1])


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add the chip with its source")
    return table[device_kind]


def least_time(cfg: dict, rows: int, device_kind: str) -> Dict[str, float]:
    """Least seconds one call of ``rows`` rows can take on the chip, and
    which bound sets it (``"ops"`` or ``"bytes"``)."""
    p = peaks(device_kind)
    t_ops = ops_per_row(cfg) * rows / p["int8_ops_per_s"]
    t_bytes = call_bytes(cfg, rows) / p["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes), "ops_s": t_ops,
            "bytes_s": t_bytes, "bound": "ops" if t_ops > t_bytes else "bytes"}
