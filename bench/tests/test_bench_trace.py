"""The reduction from a device trace to busy time, kernel time by name and
the longest idle gaps with what the host was doing."""
import gzip
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def table():
    # two devices' worth of one device: ops on device 0 only; host spans
    # nest (the window, a step inside it, a runtime event inside the step)
    return {
        "devices": 1,
        "ops": [["fusion.1", "", 100.0, 50.0, 0],
                ["fff_fused_decode", "kernel", 140.0, 30.0, 0],   # overlaps
                ["custom-call.7", "name=_gmm_dual_kernel", 300.0, 100.0, 0],
                ["fusion.2", "", 450.0, 20.0, 0],
                ["outside", "", 2000.0, 10.0, 0]],
        "host": [["bench.window", "", 50.0, 950.0],
                 ["engine.step", "", 60.0, 500.0],
                 ["TransferFromDevice", "", 190.0, 100.0],
                 ["wait.arrival", "", 600.0, 390.0]],
    }


def test_busy_is_the_union_of_ops_inside_the_window():
    t = table()
    r = trace.reduce(t, trace.window_on_profile_clock(t, "bench.window"))
    assert r.window_s == pytest.approx(950e-9)
    # [100, 170) + [300, 400) + [450, 470); "outside" is past the window
    assert r.busy_s == pytest.approx(190e-9)
    assert r.idle_share == pytest.approx(1 - 190 / 950)


def test_kernel_time_by_name_or_text():
    t = table()
    r = trace.reduce(t, (50.0, 1000.0))
    assert r.kernel_seconds("fff_fused_decode") == (pytest.approx(30e-9), 1)
    assert r.kernel_seconds(r"_gmm_kernel|_gmm_dual_kernel") == \
        (pytest.approx(100e-9), 1)
    assert r.kernel_seconds("nothing") == (0, 0)
    assert r.top_ops[0] == ["custom-call.7", pytest.approx(100e-9)]


def test_gaps_are_labelled_by_the_innermost_host_span():
    t = table()
    r = trace.reduce(t, (50.0, 1000.0))
    gaps = {round(s * 1e9): what for what, s in r.idle_gaps}
    assert gaps[530] == "wait.arrival"          # [470, 1000)
    assert gaps[130] == "TransferFromDevice"     # [170, 300), mid 235
    assert gaps[50] == "engine.step"             # [50, 100), mid 75
    assert len(r.idle_gaps) == 4                 # and [400, 450)
    assert r.idle_gaps[0][1] == pytest.approx(530e-9)


def recorded():
    with gzip.open(os.path.join(DATA, "v5e_decode_backlog_trace.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_reduces():
    """0.3 s of a traced decode-backlog window on a TPU v5e (internlm2, 32
    slots): the decode program, a chunk slab and the host's logits copies."""
    t = recorded()
    r = trace.reduce(t, trace.window_on_profile_clock(t, "bench.window"))
    assert r.window_s == pytest.approx(0.3)
    # busy = union of the ops in the window, whatever nests in what
    spans = sorted((max(s, 0.0), s + d) for _, _, s, d, _ in t["ops"])
    w0 = min(s for n, _, s, _ in t["host"] if n == "bench.window")
    covered, end = 0.0, w0
    for s, e in spans:
        s, e = max(s, end), min(e, w0 + 0.3e9)
        if e > s:
            covered += e - s
            end = e
    assert r.busy_s == pytest.approx(covered / 1e9)
    assert r.busy_s == pytest.approx(0.273529263)
    assert r.kernel_seconds("fff_fused_decode") == \
        (pytest.approx(0.014505258), 8)
    # the scan loops hold the other ops: never in the per-op breakdown
    assert not any(name.startswith("%while") for name, _ in r.top_ops)
    assert r.top_ops[0][0].startswith("%fusion.240 fusion")
    # the longest idle gaps fall where the host copies logits back
    assert r.idle_gaps[0][0] == "np.asarray(jax.Array)"
    assert 0.005 < r.idle_gaps[0][1] < 0.01
