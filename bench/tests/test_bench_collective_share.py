"""The reader ``collective_share.tp4``: per device, the union of its
collective ops' intervals inside the window over the union of all its ops
there, averaged over the devices; nothing without a trace or without a
collective."""
import types

import pytest

from bench import run as bench_run

A2A = "%all-to-all.3 = bf16[8,6144]{1,0} all-to-all(bf16[8,6144]{1,0} %p)"
AR_START = "%all-reduce-start.2 = bf16[32,6144]{1,0} all-reduce-start(%x)"
AR_DONE = "%all-reduce-done.2 = bf16[32,6144]{1,0} all-reduce-done(%s)"
GATHER = "%all-gather.7 = bf16[32,92544]{1,0} all-gather(bf16[32,23136] %l)"
FUSION = "%fusion.12 = bf16[32,6144]{1,0} fusion(bf16[32,6144] %a), kind=kLoop"
# a fusion that takes a collective's result is not a collective
USES = "%fusion.9 = f32[32,6144]{1,0} fusion(%all-reduce-done.2), kind=kLoop"
LOOP = "%while.4 = (s32[], bf16[32,6144]) while(%t), body=%body"


def _read(ops, devices):
    table = {"devices": devices, "ops": ops,
             "host": [["bench.window", "", 100.0, 1000.0]]}
    return bench_run.read_metric("collective_share.tp4",
                                 types.SimpleNamespace(trace_table=table))


def test_share_of_busy_time_averaged_over_devices():
    ops = [
        # device 0: busy [100, 600) under the loop; collectives [200, 260)
        # and the async pair [300, 320) + [340, 360): 100 of 500
        [LOOP, "", 100.0, 500.0, 0],
        [FUSION, "", 120.0, 80.0, 0],
        [A2A, "", 200.0, 60.0, 0],
        [AR_START, "", 300.0, 20.0, 0],
        [AR_DONE, "", 340.0, 20.0, 0],
        [USES, "", 360.0, 100.0, 0],
        # device 1: busy [100, 300) + [500, 700) = 400; the gather
        # [550, 650) = 100, its part before the window does not count
        [FUSION, "", 100.0, 200.0, 1],
        [GATHER, "", 500.0, 150.0, 1],
        [FUSION, "", 650.0, 50.0, 1],
        [GATHER, "", 0.0, 50.0, 1],
    ]
    assert _read(ops, 2) == pytest.approx(100 * (100 / 500 + 150 / 400) / 2)


def test_a_collective_that_straddles_the_window_counts_its_inside_part():
    ops = [[FUSION, "", 100.0, 900.0, 0], [A2A, "", 1000.0, 500.0, 0]]
    # busy [100, 1100) = 1000, of which the all_to_all [1000, 1100) = 100
    assert _read(ops, 1) == pytest.approx(10.0)


def test_reads_nothing_without_a_collective():
    ops = [[FUSION, "", 100.0, 500.0, 0], [USES, "", 700.0, 50.0, 0],
           [A2A, "", 2000.0, 50.0, 0]]            # after the window
    assert _read(ops, 1) is None


def test_reads_nothing_without_a_trace():
    run = types.SimpleNamespace(trace_table=None)
    assert bench_run.read_metric("collective_share.tp4", run) is None
