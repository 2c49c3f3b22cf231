"""``trace.py`` on the small recorded TPU profile kept beside this file
(``record_trace_fixture.py`` made it on a v5e, PR 24): four runs of one program
of one fusion, two copies each, with host sleeps between."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import trace  # noqa: E402

FIXTURE = os.path.join(HERE, "fixture.xplane.pb")


def test_fixture_reduces_to_known_numbers():
    r = trace.reduce(FIXTURE)
    # the four programs took 15765 + 18973 + 18847 + 18901 ns; the operations inside them a little less
    assert r["busy_s"] == pytest.approx(72.465e-6, rel=1e-6)
    assert sum(b - a for a, b, _ in r["modules"][0]) == pytest.approx(72486, abs=1)
    assert r["window_s"] == pytest.approx(0.013072199, rel=1e-6)  # the bench:window annotation
    ops = dict(r["device_ops"])
    assert list(ops)[0] == "convolution_tanh_fusion_bf16_1024_1024_"
    assert ops["convolution_tanh_fusion_bf16_1024_1024_"] == pytest.approx(62.978e-6, rel=1e-6)
    assert len(r["modules"][0]) == 4 and len(r["ops"][0]) == 12


def test_idle_is_what_busy_leaves_and_goes_to_the_hosts_annotations():
    r = trace.reduce(FIXTURE)
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    # the host slept 2 ms after each of four calls of well under a millisecond of device work
    assert gaps["host:fixture_sleep"] > 3 * gaps["host:fixture_call"] > 0
    assert r["shift_ns"] > 1e6  # the device's clock ran 1.28 ms behind the host's in this profile


def test_op_label_and_interval_arithmetic():
    assert trace.op_label("%fusion.12 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(bf16[16,4096] %p)") == "fusion.12_bf16_16_4096_"
    assert trace.op_label("%sort.6 = (f32[16,32000]{1,0}, s32[16,32000]{1,0}) sort(...)") == "sort.6_f32_16_32000_"
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace.clip([(0, 10)], 2, 4) == [(2, 4)]
    assert trace.attribute([(0, 10)], [(0, 10, "bench:outer"), (2, 4, "bench:inner")]) == {"host:outer": 8, "host:inner": 2}


def test_no_trace_gives_nothing_to_read():
    assert trace.reduce(None)["busy_s"] == 0.0
