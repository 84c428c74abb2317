"""xplane.py on a small trace recorded on a TPU v5e: two jitted programs
called three times inside a `bench.window` span, each call under a
`bench.evaluate` span, with a 5 ms `bench.host` sleep after it.

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests/test_xplane.py
"""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import xplane  # noqa: E402

FIXTURE = HERE / "tests" / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(str(FIXTURE))


def test_window_is_the_window_span(summary):
    assert summary.window_s == pytest.approx(0.021118599, abs=1e-9)
    assert summary.devices == ["/device:TPU:0"]


def test_busy_and_idle(summary):
    # Union of the 'XLA Ops' intervals inside the window: the device ran
    # ~121 us of a 21 ms window (three sleeps of 5 ms between the calls).
    assert summary.busy_s == pytest.approx(120.725e-6, abs=1e-9)
    assert summary.idle_share == pytest.approx(1 - 120.725e-6 / 0.021118599)
    gaps = sum(s for _, s in summary.idle_gaps)
    assert gaps + summary.busy_s == pytest.approx(summary.window_s, abs=1e-9)


def test_top_operations(summary):
    names = [n for n, _ in summary.device_ops]
    assert names[:3] == ["%sine_reduce_fusion fusion f32[]",
                         "%fusion fusion f32[1024,1024]",
                         "%copy-done copy-done f32[1024,1024]"]
    secs = [s for _, s in summary.device_ops]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == pytest.approx(82.505e-6, abs=1e-9)
    assert summary.module_seconds("jit__lambda") == pytest.approx(
        120.749e-6, abs=1e-9)
    assert summary.module_seconds("no such program") is None


def test_gaps_are_labelled_by_the_open_host_span(summary):
    # Every gap but the first lies between calls, while the host sleeps.
    assert summary.idle_gaps[0][0] == "bench.host"
    assert summary.idle_gaps[0][1] == pytest.approx(0.020997874, abs=1e-9)


@pytest.mark.parametrize("hlo,label", [
    ("%copy.98 = f32[2,10]{1,0:T(8,128)} copy(f32[2,10]{0,1} %fusion.174)",
     "%copy.98 copy f32[2,10]"),
    ("%while.22 = (s32[]{:T(128)}, s32[256]{0}) while((s32[]) %t), body=%b",
     "%while.22 while (tuple)"),
    ("no equals sign", "no equals sign"),
])
def test_op_label(hlo, label):
    assert xplane.op_label(hlo) == label


def test_union_merges_overlaps():
    assert xplane._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
