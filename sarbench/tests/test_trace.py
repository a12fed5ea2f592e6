"""The trace reduction: busy time, idle share, top operations and idle
gaps by host span, on hand-made events and on a trace recorded on the
chip."""
import json
import pathlib

import pytest

from sarbench import trace as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_op_names_drop_the_hlo_text_and_number():
    assert T.op_name('%spectral_axis1.1 = (f32[4,4096,4096]{2,1,0}) '
                     'custom-call(f32[4,4096,4096] %x.2)') == "spectral_axis1"
    assert T.op_name("%fusion.12 = f32[2] fusion(...)") == "fusion"
    assert T.op_name("copy-start") == "copy-start"


def test_union_gaps_and_overlap():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert list(T.gaps([[0, 3], [5, 8]], 0, 10)) == [(3, 5), (8, 10)]
    assert list(T.gaps([[2, 3]], 0, 3)) == [(0, 2)]
    assert T.overlap((0, 4), (3, 9)) == 1 and T.overlap((0, 1), (2, 3)) == 0


def hand_trace():
    # window 0..100 ns; device busy 10-30 (two overlapping ops), 50-60;
    # a copy 70-80; host spans h2d 0-15, dispatch 15-60, d2h 60-95
    ops = {"/device:TPU:0": [("fusion.1", 10, 25), ("spectral_axis1", 20, 30),
                             ("spectral_axis0", 50, 60),
                             ("fusion.2", 70, 80)]}
    spans = [("h2d", 0, 15), ("dispatch", 15, 60), ("d2h", 60, 95)]
    return T.Trace(ops, spans, 0, 100)


def test_busy_and_idle_by_hand():
    t = hand_trace()
    assert t.window_seconds() == pytest.approx(100e-9)
    assert t.busy_seconds() == pytest.approx(40e-9)
    assert t.idle_share() == pytest.approx(0.6)


def test_breakdown_by_hand():
    b = hand_trace().breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(15e-9)]
    idle = dict(b["idle_gaps"])
    # gaps: 0-10 (h2d), 30-50 (dispatch), 60-70 and 80-95 (d2h), 95-100
    assert idle["h2d"] == pytest.approx(10e-9)
    assert idle["dispatch"] == pytest.approx(20e-9)
    assert idle["d2h"] == pytest.approx(25e-9)
    assert idle[T.NO_SPAN] == pytest.approx(5e-9)
    assert sum(idle.values()) == pytest.approx(60e-9)


def test_overlapping_spans_of_one_name_count_once():
    # two lanes both open over the gap 10-90: 80 ns of "lane", not 160
    t = T.Trace({"/device:TPU:0": [("k", 0, 10), ("k", 90, 100)]},
                [("lane", 5, 90), ("lane", 10, 95)], 0, 100)
    assert dict(t.idle_by_span()) == {"lane": pytest.approx(80e-9)}


def test_ops_outside_the_window_do_not_count():
    t = T.Trace({"/device:TPU:0": [("a", -50, 10), ("b", 90, 200)]}, [],
                0, 100)
    assert t.busy_seconds() == pytest.approx(20e-9)


def recorded():
    doc = json.loads((DATA / "tpu_v5e_batch4_call.json").read_text())
    ops = {"/device:TPU:0": [(T.op_name(n), s, e) for n, s, e in doc["ops"]]}
    return T.Trace(ops, [tuple(s) for s in doc["spans"]], *doc["window"]), doc


def test_recorded_call_against_a_brute_force_grid():
    """One recorded closed-loop call on a v5e, reduced by the trace module
    and, independently, on a 1 us grid."""
    import numpy as np

    t, doc = recorded()
    t0, t1 = doc["window"]
    grid = np.arange(t0, t1, 1000.0) + 500.0          # 1 us cell centres
    busy = np.zeros(grid.size, bool)
    for _, s, e in doc["ops"]:
        busy |= (grid >= s) & (grid < e)
    assert t.busy_seconds() == pytest.approx(busy.sum() * 1e-6, rel=1e-3)
    assert t.idle_share() == pytest.approx(1 - busy.mean(), rel=1e-4)
    idle = dict(t.breakdown()["idle_gaps"])
    for name, s, e in doc["spans"]:
        want = (~busy & (grid >= s) & (grid < e)).sum() * 1e-6
        assert idle[name] == pytest.approx(want, rel=1e-3, abs=2e-6)
    # the copy back to the host holds the chip idle for most of the call
    assert max(idle, key=idle.get) == "d2h"


def test_recorded_call_top_ops_by_kernel_name():
    t, _ = recorded()
    top = dict(t.top_ops())
    assert list(top)[0] == "spectral_axis1"
    assert top["spectral_axis1"] == pytest.approx(15.105712e-3)
    assert top["spectral_axis0"] == pytest.approx((5231626 + 8744665) * 1e-9)
    assert sum(top.values()) <= t.busy_seconds() * (1 + 1e-9)
