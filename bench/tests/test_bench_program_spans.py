"""The readers of the program's own spans and counters, on a traced run of
the chip run's code at toy size on the CPU.

The engine's ``serve.*`` spans land in the profiler's trace nested in the
harness's ``engine.step``; the three span readers read positive times from
them and nothing from an untraced run; the leaf counter reads between one
leaf and all of them.
"""
import jax
import pytest

from bench import program_spans
from bench import run as bench_run
from bench.tests import tiny
from repro.serving import tracing

SEED = 2 ** 33 + 707
SPAN_READERS = ("prefill_chunk_ms", "logits_to_host_ms", "host_sample_ms")


def _serve(traced: bool):
    cell, cfg = tiny.cell(1, "closed")
    run, _, _ = bench_run.serve(cell, cfg, tiny.PEAKS, SEED, 1.5, traced,
                                jax.devices())
    return run, cfg


@pytest.fixture(scope="module")
def traced():
    return _serve(True)


def test_every_engine_span_nests_in_the_harness_step(traced):
    run, _ = traced
    steps = program_spans.in_window(run, "engine.step")
    assert steps
    wanted = set(tracing.ALL_SPANS) - set(tracing.SPEC)   # no speculation
    seen = set()
    for name in wanted:
        for s, e in program_spans.in_window(run, name):
            assert any(a <= s and e <= b for a, b in steps), name
            seen.add(name)
    assert seen == wanted, wanted - seen


def test_span_readers_read_the_traced_window(traced):
    run, _ = traced
    for name in SPAN_READERS:
        value = bench_run.read_metric(name, run)
        assert value is not None and value > 0, name


def test_leaf_counter_reads_between_one_leaf_and_all(traced):
    run, cfg = traced
    leaves = 2 ** cfg.period[0].ffn.fff_depth
    value = bench_run.read_metric("fff_decode_leaves", run)
    assert value is not None and 1.0 <= value <= leaves


def test_span_readers_read_nothing_untraced():
    run, _ = _serve(False)
    assert run.trace_table is None
    for name in SPAN_READERS:
        assert bench_run.read_metric(name, run) is None, name
