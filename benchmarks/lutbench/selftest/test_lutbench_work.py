"""The required-work count against numbers worked by hand."""
import json
import os

import pytest

import work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


# JSC-XL-Add2: layer 0 has 128 neurons of A=2 sub-neurons with F=1
# (2 * (1 + 1) + (1 + 2) = 7 ops each); the 64 + 64 + 64 + 5 = 197
# later neurons have F=2 (2 * (1 + 2) + 3 = 9 ops each).
# Tables: layer 0 128 * (2 * 2**7 + 2**12); layers 1-3 64 * (2 * 2**10
# + 2**12) each; layer 4 5 * (2 * 2**10 + 2**12).
# HDR-Add2: every neuron has A=2, F=4: 2 * 5 + 3 = 13 ops; 256 + 4 * 100
# + 10 = 666 neurons.  Tables: 2-bit codes, so sub tables of 2**8 and
# adder tables of 2**(2 * 3): 256 * (512 + 64) + 4 * 100 * 576
# + 10 * 576.
@pytest.mark.parametrize("name,ops,entries,n_in,n_out", [
    ("jsc-xl-add2", 128 * 7 + 197 * 9,
     128 * (256 + 4096) + 3 * 64 * (2048 + 4096) + 5 * (2048 + 4096),
     16, 5),
    ("hdr-add2", 666 * 13, 256 * 576 + 400 * 576 + 10 * 576, 784, 10),
])
def test_counts(name, ops, entries, n_in, n_out):
    cfg = config(name)
    assert work.ops_per_row(cfg) == ops
    assert work.table_entries(cfg) == entries
    assert work.call_bytes(cfg, 4096) == entries + 4096 * (n_in + n_out)


@pytest.mark.parametrize("name", ["jsc-xl-add2", "hdr-add2"])
def test_least_time_is_bytes_bound_on_v5e(name):
    cfg = config(name)
    t = work.least_time(cfg, 4096, "TPU v5 lite")
    assert t["bound"] == "bytes"
    assert t["seconds"] == pytest.approx(work.call_bytes(cfg, 4096) / 819e9)
    assert t["ops_s"] == pytest.approx(work.ops_per_row(cfg) * 4096 / 393e12)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        work.least_time(config("jsc-xl-add2"), 4096, "TPU v99")
