"""Seeded tables and the generator's stall events."""
import json
import os

import numpy as np
import pytest

import measure
import tables

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["jsc-xl-add2", "hdr-add2"])
def test_tables_follow_the_seed(name):
    cfg = config(name)
    seed = 2 ** 33 + 5                      # past 32 bits
    a, b = tables.draw_tables(cfg, seed), tables.draw_tables(cfg, seed)
    c = tables.draw_tables(cfg, seed + 1)
    for la, lb, lc, s in zip(a, b, c, tables.layer_shapes(cfg)):
        for k in ("conn", "sub", "add"):
            np.testing.assert_array_equal(la[k], lb[k])
        assert not np.array_equal(la["sub"], lc["sub"])
        assert la["conn"].shape == (s.n_out, s.adder_width, s.fan_in)
        assert la["sub"].shape == (s.n_out, s.adder_width, s.sub_entries)
        assert la["add"].shape == (s.n_out, s.add_entries)
        assert la["sub"].max() < 2 ** s.sub_bits
        assert la["add"].max() < 2 ** s.out_bits
        flat = la["conn"].reshape(s.n_out, -1)
        if flat.shape[1] <= s.n_in:          # distinct inputs per neuron
            assert all(len(set(r)) == len(r) for r in flat.tolist())


def test_stall_events_group_late_requests():
    due = np.array([0.0, 0.1, 0.2, 0.3, 2.0, 2.1, 5.0])
    late = np.array([0.001, 0.05, 0.11, 0.03, np.nan, 0.09, 0.019])
    assert measure.stall_events(due, late) == [(0.1, 0.11), (2.1, 0.09)]
