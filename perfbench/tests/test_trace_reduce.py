"""The reducer's arithmetic on hand-made events, and on one small
recorded v5e trace (`data/`, cut down by `cut_trace.py`)."""

import glob
import gzip
import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 8)]) == [
        (0, 2), (3, 5), (8, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def idle_total(reduced):
    return sum(s for name, s in reduced["idle_gaps"]
               if name.startswith("all gaps "))


def nested_instructions():
    text = ("%while.9 = (s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[2,8]{1,0}) %tuple.1), "
            "condition=%cond, body=%body")
    assert tr.instruction(text) == (
        "while.9", "while", "while.9 = (s32[], bf16[2,8]) while")
    fusion = ("%fusion.4 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]"
              "{1,0} %p), kind=kOutput, calls=%fused_computation.4")
    assert tr.instruction(fusion)[1:] == (
        "fusion kOutput", "fusion.4 = bf16[2,8] fusion kOutput")
    return text, fusion


def test_self_time_of_nested_instructions():
    loop, fusion = nested_instructions()
    assert tr.self_times([(loop, 0, 100), (fusion, 10, 40),
                          (fusion, 50, 90), ("copy.1", 100, 110)]) == {
        loop: 30, fusion: 70, "copy.1": 10}
    assert tr.instruction("copy.1") == ("copy.1", "copy", "copy.1")


def hand_made(n_chips=1):
    ops = [("fusion.1", 100, 340), ("all-reduce-start.2", 340, 350),
           ("fusion.3", 350, 500), ("all-reduce-done.2", 500, 600),
           ("fusion.1", 700, 900),
           ("copy.4", 1500, 1600)]          # the last one after the window
    planes = {f"/device:TPU:{i}": {
        tr.OPS_LINE: list(ops),
        tr.ASYNC_LINE: [("all-reduce-start.2", 340, 600)],
        "XLA Modules": [("jit_step", 100, 900)]} for i in range(n_chips)}
    planes["/host:CPU"] = {"main": [
        ("perfbench.step", 0, 500), ("perfbench.step", 500, 1000),
        ("perfbench.dispatch", 0, 50), ("perfbench.loss_read", 50, 480),
        ("perfbench.dispatch", 600, 650),
        ("perfbench.loss_read", 650, 1000), ("other", 0, 1000)]}
    return planes


def test_reduction_of_hand_made_events():
    got = tr.reduce(hand_made(), n_chips=1)
    assert got["steps"] == 2
    assert got["window_s"] == 1000e-12
    assert got["busy_s"] == got["busy_first_chip_s"] == 700e-12
    # busy + idle = span
    assert idle_total(got) + got["busy_s"] == \
        pytest.approx(got["window_s"])
    assert got["collective_ops"] == ["all-reduce-done.2",
                                     "all-reduce-start.2"]
    # in flight 340-600; fusion.3 hides 350-500 of it
    assert got["collective_s"] == 260e-12
    assert got["collective_exposed_s"] == 110e-12
    assert got["device_ops"][0] == ["fusion.1 (62.9% of busy)", 440e-12]
    # gaps: 0-100 (dispatch 0-50, loss_read 50-100), 600-700 (dispatch
    # 600-650, loss_read 650-700), 900-1000 (loss_read)
    assert dict(map(tuple, got["idle_gaps"][:3])) == {
        "all gaps during perfbench.loss_read": 200e-12,
        "all gaps during perfbench.dispatch": 100e-12,
        "all gaps outside any perfbench span": 0.0}
    assert got["idle_gaps"][3] == [
        "longest gap 1, mostly during perfbench.dispatch", 100e-12]
    assert len(got["idle_gaps"]) == 6
    assert got == tr.reduce(hand_made(), n_chips=1)


def test_wrong_chip_count_and_empty_window_are_errors():
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce(hand_made(1), n_chips=4)
    planes = hand_made()
    planes["/device:TPU:0"][tr.OPS_LINE] = [("copy.4", 1500, 1600)]
    with pytest.raises(ValueError, match="no operation"):
        tr.reduce(planes, n_chips=1)


@pytest.mark.parametrize("packed", sorted(glob.glob(
    os.path.join(DATA, "*.chips?.xplane.pb.gz"))))
def test_recorded_trace(packed, tmp_path):
    """`<cell>.chips<n>.xplane.pb.gz`, recorded on the v5e and cut."""
    n_chips = int(packed.split(".chips")[1][0])
    path = str(tmp_path / "trace.xplane.pb")
    with gzip.open(packed) as src, open(path, "wb") as dst:
        dst.write(src.read())
    got = tr.reduce_file(path, n_chips)
    assert got == tr.reduce_file(path, n_chips)
    assert got["steps"] == 2 and 0 < got["busy_s"] <= got["window_s"]
    assert idle_total(got) + got["busy_first_chip_s"] == \
        pytest.approx(got["window_s"])
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) <= 10
    # the four-chip cell's gradient all-reduces: 8 instructions a
    # step, synchronous, so nothing hides them
    assert len(got["collective_ops"]) == 8
    assert all("all-reduce" in op or "psum" in op
               for op in got["collective_ops"])
    assert 0 < got["collective_exposed_s"] <= got["collective_s"]
    assert got["collective_s"] / got["steps"] == pytest.approx(
        35.3e-3, rel=0.01)
