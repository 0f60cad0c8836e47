"""chip_smoke.py on the CPU: its serving phase at a tiny width in
interpret mode, and its refusal to run without a TPU."""
from __future__ import annotations

import importlib.util
import pathlib

import jax

from repro.core import lut_synth as LS
from repro.core import lutdnn as LD

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_bit_exact_tiny(tmp_path):
    cs = _chip_smoke()
    spec = LD.ModelSpec(name="smoke-tiny", in_features=16, widths=(16, 5),
                        bits=2, fan_in=2, degree=1, adder_width=2,
                        input_bits=7, input_fan_in=1)
    tables = LS.synthesise(LD.init_model(jax.random.key(0), spec), spec)
    codes = cs.input_codes(spec, 150)
    r = cs.serve_phase(spec, tables, codes, str(tmp_path), microbatch=64)
    assert r["served"] == len(codes) and r["failed"] == 0
    assert r["mismatches"] == 0
    # the served program is the interpreter here, not a TPU kernel
    assert not r["kernel_in_program"]
    assert r["plan"].startswith("plan: fused")
    # serve_phase raised unless the unpacked load equals the
    # synthesised tables; the packed load kept its int4 slabs packed
    assert r["packed_load"]
    assert len(r["artifact_id"]) == 64


def test_main_refuses_without_tpu(capsys):
    rc = _chip_smoke().main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
