"""trace_reduce.py on a hand-made trace (exact numbers) and on a cut of a
trace recorded on the chip (``data/``; what the planes, lines and names of a
TPU v5 lite trace look like)."""

import glob
import json
import os

import pytest

import trace_reduce

MS = 1_000_000  # ns


def planes(device_ops, host, second_device=()):
    out = [{"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
           {"name": "/device:TPU:0", "lines": [
               {"name": "XLA Modules", "events": []},
               {"name": "XLA Ops", "events": list(device_ops)}]},
           {"name": "/device:CUSTOM:Megascale Trace", "lines": [
               {"name": "XLA Ops", "events": [["%noise", 0, 900 * MS]]}]}]
    if second_device:
        out.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": list(second_device)}]})
    return out


HOST = [["bench.query", 100 * MS, 100 * MS],
        ["bench.parse", 100 * MS, 2 * MS],
        ["bench.execute", 102 * MS, 80 * MS],
        ["bench.fetch", 182 * MS, 18 * MS],
        ["bench.query", 210 * MS, 100 * MS],
        ["bench.parse", 210 * MS, 2 * MS],
        ["bench.execute", 212 * MS, 80 * MS],
        ["bench.fetch", 292 * MS, 18 * MS],
        ["something else", 0, 500 * MS]]
OPS = [["%warmup = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10 * MS, 20 * MS],
       ["%while.1 = (s32[]) while((s32[]) %t), body=%b", 104 * MS, 70 * MS],
       ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", 110 * MS, 30 * MS],
       ["%sort.3 = f32[8]{0} sort(f32[8]{0} %y)", 174 * MS, 6 * MS],
       ["%slice.4 = f32[4]{0} slice(f32[8]{0} %z)", 190 * MS, 1 * MS],
       ["%while.1 = (s32[]) while((s32[]) %t), body=%b", 214 * MS, 70 * MS],
       ["%all-reduce.5 = f32[4]{0} all-reduce(f32[4]{0} %w)", 284 * MS, 4 * MS],
       ["%fusion.6 = f32[4]{0} fusion(f32[4]{0} %all-reduce.5), kind=kLoop",
        288 * MS, 2 * MS],
       ["%late = f32[8]{0} copy(f32[8]{0} %q)", 305 * MS, 20 * MS]]


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                                   [5, 9]]


def test_busy_is_the_union_inside_the_traced_window():
    r = trace_reduce.reduce(planes(OPS, HOST))
    assert r["queries"] == 2
    assert r["window_s"] == pytest.approx(0.210)
    assert r["devices"] == ["/device:TPU:0"]  # the CUSTOM plane is no chip
    # while 70 (the fusion inside it adds nothing) + sort 6 + slice 1, then
    # while 70 + all-reduce 4 + fusion 2 + the 5 ms of %late before the
    # window closes; %warmup lies before it
    assert r["busy_s"] == r["busiest_busy_s"] == pytest.approx(0.158)
    assert r["ops"][0] == ["%while.1 while", pytest.approx(0.140)]
    assert ["%fusion.2 fusion kLoop", pytest.approx(0.030)] in r["ops"]


def test_idle_time_is_booked_to_the_span_it_falls_in():
    r = trace_reduce.reduce(planes(OPS, HOST))
    gaps = dict(r["gaps"])
    # busy: 104-180, 190-191, 214-290, 305-310 of the window 100-310
    assert gaps["bench.parse"] == pytest.approx(0.002 + 0.002)
    assert gaps["bench.execute"] == pytest.approx(0.002 + 0.002 + 0.002 + 0.002)
    assert gaps["bench.fetch"] == pytest.approx(0.008 + 0.009 + 0.013)
    assert gaps["between queries"] == pytest.approx(0.010)
    assert "bench.query" not in gaps  # the three spans fill each query
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["gaps"][0][0] == "bench.fetch"  # ranked, largest first


def test_a_collective_is_found_by_the_name_of_the_op_not_of_its_operand():
    r = trace_reduce.reduce(planes(OPS, HOST))
    assert r["collective_ops"] == 1
    assert r["collective_s"] == pytest.approx(0.004)


def test_several_chips_average_busy_and_name_the_busiest():
    r = trace_reduce.reduce(planes(OPS, HOST, second_device=[
        ["%while.1 = (s32[]) while((s32[]) %t), body=%b", 104 * MS, 40 * MS]]))
    assert r["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert r["busiest"] == "/device:TPU:0"
    assert r["busy_s"] == pytest.approx((0.158 + 0.040) / 2)


def test_a_trace_with_no_device_op_reduces_to_zero_busy():
    r = trace_reduce.reduce(planes([], HOST))
    assert r["devices"] == [] and r["busy_s"] == 0.0


def test_a_trace_without_our_spans_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes(OPS, [["something else", 0, MS]]))


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.planes.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    with open(path) as f:
        recorded = json.load(f)
    r = trace_reduce.reduce(recorded)
    assert r["queries"] >= 1
    assert all(d.startswith("/device:TPU:") for d in r["devices"])
    assert 0 < r["busy_s"] <= r["busiest_busy_s"] <= r["window_s"]
    assert sum(s for _, s in r["gaps"]) + r["busiest_busy_s"] == \
        pytest.approx(r["window_s"])
    assert {name for name, _ in r["gaps"]} <= {
        "bench.parse", "bench.execute", "bench.fetch", "bench.query",
        "between queries"}
    # an independent reading of the same trace: the ops of a module fill it,
    # so busy time by ops is the modules' own durations, within 2%
    modules = sum(d for plane in recorded if plane["name"] in r["devices"]
                  for line in plane["lines"] if line["name"] == "XLA Modules"
                  for _, _, d in line["events"]) / 1e9
    assert r["busy_s"] * len(r["devices"]) == pytest.approx(modules, rel=0.02)
    # and the reduction as it was when the fixture was recorded
    with open(path.replace(".planes.json", ".expected.json")) as f:
        for key, value in json.load(f).items():
            assert r[key] == pytest.approx(value, rel=1e-6), key
