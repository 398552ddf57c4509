"""The trace reduction: busy union, idle gaps, kernel calls, the
attribution of gaps to the benchmark's host spans, and the HLO text the
TPU profiler names its events by; on small made-up traces and on an
excerpt of a trace recorded on a TPU v5e (the paper's MNIST experiment:
the initial round's aggregation and K-means distance calls)."""
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, run, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000  # ns
AGG = [["f32", [1, 512]], ["f32", [1, 40]], ["f32", [40, 512]]]


def small():
    """One device; window 0-100 us; a build span 0-10, a dispatch 10-90."""
    ops = [["flat_aggregate.1", 5 * US, 3 * US, "custom-call"],
           ["fusion.2", 20 * US, 10 * US, "fusion"],
           ["fusion.3", 25 * US, 10 * US, "fusion"],           # overlaps
           ["while.7", 20 * US, 15 * US, "while"],             # container
           ["pairwise_l2.4", 60 * US, 20 * US, "custom-call"],
           ["fusion.5", 120 * US, 5 * US, "fusion"]]           # outside
    calls = [["flat_aggregate", 5 * US, 3 * US, AGG],
             ["flat_aggregate", 120 * US, 3 * US, AGG]]        # outside
    host = [["window", 0, 100 * US], ["build", 0, 10 * US],
            ["dispatch", 10 * US, 80 * US]]
    return {"devices": {"0": ops}, "calls": {"0": calls}, "host": host}


def test_busy_is_the_union_within_the_window():
    red = trace.reduce(small())
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"]["0"] == pytest.approx((3 + 15 + 20) * 1e-6)


def test_idle_gaps_are_labelled_by_the_host_span_over_most_of_them():
    red = trace.reduce(small())
    gaps = {round(s * 1e6): name for name, s in red["idle_gaps"]}
    # 0-5 under build; 8-20 mostly dispatch; 35-60 dispatch; 80-100 half
    # dispatch, half nothing: no span covers most of it
    assert gaps == {5: "build", 12: "dispatch", 25: "dispatch", 20: "none"}
    assert [round(s * 1e6) for _, s in red["idle_gaps"]] == [25, 20, 12, 5]


def test_ops_are_summed_by_instruction_and_containers_left_out():
    red = trace.reduce(small())
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "pairwise_l2.4" and "while.7" not in names
    assert red["device_ops"][0][1] == pytest.approx(20e-6)


def test_kernel_calls_within_the_window_are_kept():
    red = trace.reduce(small())
    assert red["calls"]["0"] == [["flat_aggregate", 5 * US, 3 * US, AGG]]


def test_innermost_span_wins():
    tr = small()
    tr["host"].append(["build", 40 * US, 15 * US])   # nested in dispatch
    gaps = {round(s * 1e6): name for name, s in trace.reduce(tr)
            ["idle_gaps"]}
    assert gaps[25] == "build"


def test_two_devices_each_get_their_own_busy_time():
    tr = small()
    tr["devices"]["1"] = [["fusion.9", 0, 50 * US, "fusion"]]
    red = trace.reduce(tr)
    assert red["busy_s"]["1"] == pytest.approx(50e-6)
    assert red["busy_s"]["0"] == pytest.approx(38e-6)


def test_a_trace_without_window_span_is_refused():
    tr = small()
    tr["host"] = tr["host"][1:]
    with pytest.raises(KeyError):
        trace.reduce(tr)


def test_hlo_text_of_tpu_events():
    text = ('%pairwise_l2.28 = f32[40,10]{1,0:T(8,128)S(1)} custom-call('
            'f32[40,2560]{1,0:T(8,128)S(1)} %pad.498, f32[10,2560]{1,0:T('
            '8,128)S(1)} %pad.499), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[40,2560]}')
    assert trace.parse_op(text) == ("pairwise_l2.28", "custom-call")
    assert trace.shapes(text) == [["f32", [40, 10]], ["f32", [40, 2560]],
                                  ["f32", [10, 2560]]]
    tup = ('%compare_select_fusion.43 = (f32[40]{0:T(128)S(1)}, f32[40]{0:'
           'T(128)S(1)}) fusion(f32[40]{0:T(128)S(1)} %x)')
    assert trace.parse_op(tup) == ("compare_select_fusion.43", "fusion")
    assert trace.nbytes(["bf16", [10, 32, 24]]) == 2 * 10 * 32 * 24


def test_recorded_excerpt():
    with open(os.path.join(DATA, "trace_excerpt.json")) as f:
        tr = json.load(f)
    red = trace.reduce(tr)
    assert 0 < red["busy_s"]["0"] <= red["window_s"] == pytest.approx(3e-3)
    kinds = {}
    for c in red["calls"]["0"]:
        kinds.setdefault((c[0], json.dumps(c[3])), []).append(c)
    # the initial round: one aggregation of all 40 clients over the padded
    # plane, then K-means (9 seeding calls, 50 Lloyd, 1 final) on the
    # padded [40, 2240] w_fc2 columns against 10 centroids
    agg = json.dumps([["f32", [1, 114176]], ["f32", [1, 40]],
                      ["f32", [40, 114176]]])
    km = json.dumps([["f32", [40, 10]], ["f32", [40, 2560]],
                     ["f32", [10, 2560]]])
    assert len(kinds[("flat_aggregate", agg)]) == 1
    assert len(kinds[("pairwise_l2", km)]) == 60
    assert {g[0] for g in red["idle_gaps"]} <= {"dispatch", "none"}
    ctx = SimpleNamespace(red=red, peaks=flops.peaks("TPU v5 lite"))
    for k in ("flat_aggregate", "pairwise_l2"):
        assert 0 < run.load_metric(k + "_roofline").read(ctx) <= 100
