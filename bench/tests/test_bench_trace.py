"""The trace reduction on events made by hand: busy time as a union of
intervals, per-kernel and per-program sums, and idle gaps named by the
host span they fall in."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return tr.Event(plane, line, name, float(start), float(dur))


@pytest.fixture
def events():
    return [
        ev("python", tr.WINDOW_SPAN, 100, 1000, plane=tr.HOST_PLANE),
        ev("python", "stream_search", 100, 500, plane=tr.HOST_PLANE),
        ev("python", "frontend.wait", 600, 300, plane=tr.HOST_PLANE),
        # ops: [50,150] straddles the window start; [200,300] and
        # [250,400] overlap; [900,950]; [1050,1200] straddles the end
        ev(tr.OPS_LINE, "_distance_kernel", 50, 100),
        ev(tr.OPS_LINE, "_bitonic_body", 200, 100),
        ev(tr.OPS_LINE, "fusion.1", 250, 150),
        ev(tr.OPS_LINE, "_merge_body", 900, 50),
        ev(tr.OPS_LINE, "_distance_kernel", 1050, 150),
        ev(tr.MODULES_LINE, "jit_engine_run_chunk_admit(7)", 200, 200),
        ev(tr.MODULES_LINE, "jit_engine_retire(3)", 900, 50),
    ]


def test_idle_share_is_one_minus_union_over_window(events):
    red = tr.reduce(events, ["stream_search", "frontend.wait"])
    assert red.window_s == pytest.approx(1000e-9)
    # busy: [100,150] 50 + [200,400] 200 + [900,950] 50 + [1050,1100] 50
    assert red.busy_s == pytest.approx(350e-9)


def test_kernel_and_program_sums(events):
    red = tr.reduce(events, [])
    assert red.op_time_s(r"_distance_kernel") == pytest.approx(100e-9)
    assert red.op_time_s(r"_bitonic_body|_merge_body") == pytest.approx(150e-9)
    assert red.module_time_s(r"engine_run_chunk") == pytest.approx(200e-9)
    top = dict(red.device_ops)
    assert top["fusion.1"] == pytest.approx(150e-9)
    assert top["_distance_kernel"] == pytest.approx(100e-9)


def test_gaps_named_by_overlapping_host_span(events):
    red = tr.reduce(events, ["stream_search", "frontend.wait"])
    # gaps: [150,200] 50, [400,900] 500, [950,1050] 100
    names = [(n, pytest.approx(s)) for n, s in red.idle_gaps]
    assert names == [("frontend.wait", 500e-9), ("other", 100e-9),
                     ("stream_search", 50e-9)]


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 6]]


def test_no_device_plane_is_an_error(events):
    host_only = [e for e in events if e.plane == tr.HOST_PLANE]
    with pytest.raises(ValueError):
        tr.reduce(host_only, [])


def test_busy_time_over_the_cells_own_devices(events):
    # a second chip of the host, one the cell does not use, ran one op
    other = events + [ev(tr.OPS_LINE, "fusion.2", 100, 100,
                         plane="/device:TPU:1")]
    assert tr.reduce(other, [], [0]).busy_s == pytest.approx(350e-9)
    assert tr.reduce(other, [], [1]).busy_s == pytest.approx(100e-9)
    assert tr.reduce(other, []).busy_s == pytest.approx(225e-9)
    with pytest.raises(ValueError, match="cell's"):
        tr.reduce(other, [], [2])
