"""The reader ``prefill_slab_rows``: the mean of the program's counter
``prefill.slab_rows`` over the samples inside the window, and nothing on a
program that records no such counter; on a traced run of the chip run's
code at toy size on the CPU it reads between one row and every slot."""
import collections
import types

import jax
import pytest

from bench import run as bench_run
from bench.tests import tiny
from repro.serving import tracing

SEED = 2 ** 33 + 707


@pytest.fixture
def log(monkeypatch):
    """An empty counter log for this test alone."""
    fresh = collections.deque(maxlen=tracing.MAX_SAMPLES)
    monkeypatch.setattr(tracing, "_log", fresh)
    return fresh


def _read(t_open: float, t_close: float):
    window = types.SimpleNamespace(t_open=t_open, t_close=t_close)
    return bench_run.read_metric("prefill_slab_rows",
                                 types.SimpleNamespace(window=window))


def test_reads_the_mean_of_the_samples_inside_the_window(log):
    engine = tracing.new_engine_id()
    for t, rows in [(0.5, 32), (1.0, 2), (1.5, 4), (2.0, 1), (2.5, 16)]:
        tracing.record(t, engine, "prefill.slab_rows", rows)
    assert _read(1.0, 2.5) == pytest.approx((2 + 4 + 1) / 3)
    assert _read(0.0, 1.0) == pytest.approx(32.0)


@pytest.mark.parametrize("other", [None, "fff.decode_leaves"],
                         ids=["empty", "other_counters"])
def test_reads_nothing_without_the_counter(log, other):
    if other is not None:
        tracing.record(1.0, tracing.new_engine_id(), other, 13.0)
    assert _read(0.0, 10.0) is None


def test_reads_nothing_when_no_slab_falls_in_the_window(log):
    tracing.record(5.0, tracing.new_engine_id(), "prefill.slab_rows", 2)
    assert _read(0.0, 5.0) is None


def test_reads_between_one_row_and_every_slot_on_a_traced_run():
    cell, cfg = tiny.cell(1, "closed")
    run, _, _ = bench_run.serve(cell, cfg, tiny.PEAKS, SEED, 1.5, True,
                                jax.devices())
    value = bench_run.read_metric("prefill_slab_rows", run)
    assert value is not None
    assert 1.0 <= value <= run.mix["engine"]["num_slots"]
