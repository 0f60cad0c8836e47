"""Whole runs of small cells on the CPU, with the timed path broken
underneath: ``correct`` has to come out false for each fault a serving
cell can have, and for the control (the reference on tables one integer
width lower).  The look for a chip is skipped; everything else is the
run as the benchmark makes it."""
import json
import os

import numpy as np
import pytest

import bench
import reference
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = ["tiny.stream", "tiny.bulk"]


def tiny_benchmark():
    with open(os.path.join(DATA, "benchmark.json")) as f:
        return json.load(f)


def run_cell(cell, capsys, seed=2 ** 31 + 7):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", "0"], benchmark=tiny_benchmark(),
                  traffic_dir=os.path.join(DATA, "traffic"),
                  require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return result


def _alter_one_answer(orig):
    def broken(tables, codes, plan, force_interpret):
        out = orig(tables, codes, plan, force_interpret)
        return out.at[0, 0].set(out[0, 0] ^ 1)
    return broken


def _leave_out_half(orig):
    def broken(tables, codes, plan, force_interpret):
        out = orig(tables, codes, plan, force_interpret)
        return out.at[1::2].set(0)         # every other row not served
    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    result = run_cell(cell, capsys)
    assert result["correct"] is True
    assert result["checks"]["mismatched_rows"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
def test_broken_engine_is_not_correct(cell, fault, capsys, monkeypatch):
    from repro.kernels.lut_gather import ops

    monkeypatch.setattr(ops, "_execute_plan", fault(ops._execute_plan))
    result = run_cell(cell, capsys)
    assert result["correct"] is False
    assert result["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The control in the program's place, at test size: its answers
    differ from the reference's, so the check's limit of 0 fails."""
    import contextlib

    c = bench.load_cell(tiny_benchmark(), cell,
                        traffic_dir=os.path.join(DATA, "traffic"))
    drv = bench.driver(c.traffic["kind"])
    with contextlib.ExitStack() as stack:
        import tempfile

        workdir = stack.enter_context(tempfile.TemporaryDirectory())
        st = drv.setup(c, 5, 0.5, workdir, lambda _: contextlib.nullcontext())
    m = drv.window(st, 0.5, run.Tracer(None))
    drv.close(st)
    value, limit = drv.check(st, m, lower_precision=True)["mismatched_rows"]
    assert value > limit == 0


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "tiny.bulk", "--seed", "1", "--seconds",
                   "0.5"], benchmark=tiny_benchmark(),
                  traffic_dir=os.path.join(DATA, "traffic"))
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "TPU" in err


def test_reference_agrees_with_the_program_reference():
    """The benchmark's own reference against the program's jnp oracle
    chain (kernels/lut_gather/ref.py) on seeded tables: the two were
    written apart and must agree exactly."""
    import jax.numpy as jnp

    import tables
    from repro.kernels.lut_gather import ref

    c = bench.load_cell(tiny_benchmark(), "tiny.bulk",
                        traffic_dir=os.path.join(DATA, "traffic"))
    layers = tables.draw_tables(c.config, 11)
    x = tables.input_codes(c.config, 300, tables.host_rng(11, 0))
    got = jnp.asarray(x)
    for layer in layers:
        s = layer["shape"]
        got = ref.lut_layer(got, jnp.asarray(layer["conn"]),
                            jnp.asarray(layer["sub"]),
                            jnp.asarray(layer["add"]), s.in_bits, s.sub_bits)
    np.testing.assert_array_equal(np.asarray(got),
                                  reference.forward(layers, x))
