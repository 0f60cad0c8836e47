"""The trace reducer against a hand-built trace with known answers, and
against a small trace recorded on a TPU v5 lite."""
import gzip
import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000_000          # picoseconds per microsecond

KERNEL = ('%k.1 = s32[5,4096] custom-call(s32[16,4096] %p), '
          'custom_call_target=\\"tpu_custom_call\\"')


def _events(spec):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US} }}" for m, a, b in spec)


def _meta(names):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())


# One 100 us window.  Device ops (us): the kernel at [10, 30) and
# [50, 70), a fusion at [25, 40) that overlaps the first, and an op at
# [95, 120) that the window cuts at 100.  Busy: [10, 40) + [50, 70) +
# [95, 100) = 55 us.  Program runs: [9, 41) and [49, 71) hold a kernel;
# [94, 121) ends past the window.  Idle gaps: [0, 10) with no host
# event, [40, 50) under the benchmark's fetch span, [70, 95) half
# under device_put (10 us, under half) and most under a runtime
# Transpose (23 us).
HAND_BUILT = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 10, 30), (2, 25, 40), (1, 50, 70), (3, 95, 120)])} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(4, 9, 41), (4, 49, 71), (4, 94, 121)])} }}
  {_meta({1: KERNEL, 2: "%fusion.2 = f32[8] fusion()",
          3: "%late.3 = f32[8] copy()", 4: "jit_fn(1)"})}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "main" timestamp_ns: 0
    {_events([(1, 0, 100), (2, 40, 50), (3, 70, 80)])} }}
  lines {{ id: 2 name: "pjrt" timestamp_ns: 0
    {_events([(4, 72, 95)])} }}
  {_meta({1: "lutbench.window", 2: "lutbench.fetch",
          3: "lutbench.device_put", 4: "Transpose"})}
}}
"""


@pytest.fixture(scope="module")
def hand_built():
    from jax.profiler import ProfileData

    return trace_reduce.reduce(ProfileData.from_text_proto(HAND_BUILT))


def test_window_busy_and_idle(hand_built):
    s = hand_built
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(55e-6)
    assert s.idle_share == pytest.approx(0.45)


def test_kernels_and_program_runs(hand_built):
    assert hand_built.kernel_s == pytest.approx(40e-6)
    assert hand_built.kernel_runs == 2


def test_device_ops_by_short_name(hand_built):
    ops = dict(hand_built.device_ops)
    assert ops["k.1"] == pytest.approx(40e-6)
    assert ops["fusion.2"] == pytest.approx(15e-6)
    assert ops["late.3"] == pytest.approx(5e-6)


def test_idle_gaps_labelled_by_host_spans(hand_built):
    gaps = dict(hand_built.idle_gaps)
    assert gaps == pytest.approx({
        "lutbench.fetch": 10e-6, "Transpose": 25e-6,
        "no host span over half the gap": 10e-6})


def test_a_trace_without_window_is_refused():
    from jax.profiler import ProfileData

    no_window = HAND_BUILT.replace('name: "lutbench.window"',
                                   'name: "something.else"')
    with pytest.raises(ValueError):
        trace_reduce.reduce(ProfileData.from_text_proto(no_window))


RECORDED = os.path.join(DATA, "jsc-bulk.xplane.pb.gz")


def test_recorded_chip_trace():
    """A 0.3 s traced window of jsc-xl-add2.bulk on a TPU v5 lite; the
    expected numbers are those the reducer gave when it was recorded,
    beside invariants any trace of that cell keeps."""
    with open(os.path.join(DATA, "jsc-bulk.expected.json")) as f:
        want = json.load(f)
    s = trace_reduce.reduce(trace_reduce.load(RECORDED))
    assert 0 < s.busy_s <= s.window_s
    assert s.kernel_s <= s.busy_s * (1 + 1e-9)
    assert s.kernel_runs > 0
    assert s.window_s == pytest.approx(want["window_s"])
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert s.kernel_s == pytest.approx(want["kernel_s"])
    assert s.kernel_runs == want["kernel_runs"]
    assert gzip.open(RECORDED).read(4)     # the file is whole
