"""Dry-run integration smoke: lower+compile a reduced cell on a tiny
placeholder mesh in a subprocess (the production 256/512-chip sweep
lives in runs/dryrun; this guards the machinery in CI)."""
import json
import os
import subprocess
import sys

import pytest

ENV = {**os.environ, "PYTHONPATH": "src"}


def _run(args, timeout=200):
    # inside the 240 s per-test limit (tests/time_limit.py), so a stuck
    # child fails as a TimeoutExpired of its own
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + args,
        capture_output=True, text=True, timeout=timeout, env=ENV,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("arch,shape", [
    ("qwen2.5-3b", "train_4k"),
    ("granite-moe-1b-a400m", "decode_32k"),
    ("whisper-tiny", "prefill_32k"),
])
def test_dryrun_cell_smoke(tmp_path, arch, shape):
    out = str(tmp_path / "cell.json")
    r = _run(["--arch", arch, "--shape", shape, "--mesh", "2x2",
              "--smoke", "--out", out])
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    assert rec["cost"]["flops_per_chip"] > 0
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert rec["memory"]["peak_bytes_per_device"] > 0
    # all three roofline terms are non-negative
    rf = rec["roofline"]
    assert min(rf["compute_s"], rf["memory_s"], rf["collective_s"]) >= 0


def test_dryrun_multipod_mesh_smoke(tmp_path):
    """The `pod` axis shards: a 3-axis mesh compiles the same cell."""
    out = str(tmp_path / "cell.json")
    r = _run(["--arch", "qwen2.5-3b", "--shape", "train_4k",
              "--mesh", "2x2x2", "--smoke", "--out", out])
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    assert rec["chips"] == 8
