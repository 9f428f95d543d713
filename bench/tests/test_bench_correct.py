"""The run's own code on the CPU at a toy size: set-up, the window, the
drain, the metrics and the comparison that decides ``correct``.

The chip's look for an accelerator is skipped (``run.serve`` takes the CPU
device); everything after it is the code a chip run executes.  One run
per loop and model shape is served as is and must come out correct; the
control (the reference in float8 put in the program's place) must come out
not correct; and each fault the served path can have (a token altered where
it is produced, a decode step that hands back its cache unchanged) must
make ``correct`` false.
"""
import jax
import numpy as np
import pytest

from bench import correct, reference
from bench import run as bench_run
from bench.tests import tiny

SEED = 2 ** 33 + 101
# the toy model in bf16 against the float32 reference: its mean gap over a
# sample read 0.0002-0.04 on eight seeds, its float8 control 0.09-0.35
LIMIT = {"mean_logit_gap": 0.07}


def serve(trees=1, loop="closed", seconds=1.5, seed=SEED):
    cell, cfg = tiny.cell(trees, loop, LIMIT)
    run, params, window = bench_run.serve(cell, cfg, tiny.PEAKS, seed,
                                          seconds, False, jax.devices())
    return cell, run, params, window


@pytest.fixture(scope="module")
def closed_forest():
    return serve(trees=2)


@pytest.fixture(scope="module")
def open_tree():
    return serve(trees=1, loop="open")


def check(cell, params, window, seed=SEED, control=False):
    return correct.check(params, cell.config, window, seed,
                         cell.traffic["check_requests"],
                         cell.traffic["engine"]["max_len"], cell.limits,
                         control=control)


@pytest.mark.parametrize("which", ["closed_forest", "open_tree"])
def test_served_tokens_match_the_reference(which, request):
    cell, run, params, window = request.getfixturevalue(which)
    v = check(cell, params, window)
    assert v.ok, v.checks
    assert v.n > 0 and 0 <= v.checks["mean_logit_gap"]["value"] <= 0.07


@pytest.mark.parametrize("which", ["closed_forest", "open_tree"])
def test_window_records_feed_the_metrics(which, request):
    cell, run, params, window = request.getfixturevalue(which)
    assert window.compiles_in_window == 0
    assert window.shapes_before == window.shapes_after
    assert any(s.decode_rows for s in window.steps)
    # the engine's decode latencies of the window's steps only (not the
    # warm-up's or the closed loop's ramp)
    assert 0 < len(window.decode_lat) <= len(window.steps)
    if cell.traffic["loop"] == "closed":
        # the ramp: every client sent before the window opened, and the
        # window opened once warm_completions requests had finished
        before = [s for s in window.requests.values()
                  if s.arrival < window.t_open]
        assert window.t_first < window.t_open
        assert len(before) >= cell.traffic["clients"] \
            + cell.traffic["warm_completions"]
    assert any(s.prefill_tokens for s in window.steps)
    for name in ("output_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s",
                 "decode_step_ms", "mfu.backlog"):
        v = bench_run.read_metric(name, run)
        assert v is not None and v > 0, name
    # a traced run's readers find nothing to read without a trace
    for name in ("idle_share.backlog", "fff_fused_decode_roofline"):
        assert bench_run.read_metric(name, run) is None
    arrived = [s for s in window.requests.values()
               if window.t_open <= s.arrival < window.t_close]
    assert arrived and all(s.first_token is not None for s in arrived)
    for s in arrived:
        assert s.first_token >= s.arrival
        assert s.token_times == sorted(s.token_times)


def test_control_reads_wider_gaps(closed_forest):
    """The control at this size, through the check a run makes: float8
    ranks other tokens first, and the limit fails it."""
    cell, run, params, window = closed_forest
    v = check(cell, params, window, control=True)
    assert not v.ok
    assert v.control.mean() > LIMIT["mean_logit_gap"] > v.program.mean()
    assert (v.control > 0).mean() > 2 * (v.program > 0).mean()
    assert v.program.shape == v.control.shape == v.margin.shape


def _altered_token(out, caches):
    """Every decode step's logits favour another token."""
    logits = out[0]
    wrong = (jax.numpy.argmax(logits, axis=-1) + 1) % logits.shape[-1]
    bump = jax.nn.one_hot(wrong, logits.shape[-1], dtype=logits.dtype)
    return (logits + 1e3 * bump,) + tuple(out[1:])


def _cache_unchanged(out, caches):
    """The decode step hands back the cache it was given: no K/V written,
    no length advanced."""
    return (out[0], caches) + tuple(out[2:])


def _served_with_fault(fault, monkeypatch):
    """A fault planted in the program's decode step, underneath the
    engine: the run's own check must come out not correct."""
    from repro.models import lm
    decode = lm.decode_step

    def broken(params, cfg, token, caches, *args, **kw):
        return fault(decode(params, cfg, token, caches, *args, **kw), caches)

    monkeypatch.setattr(lm, "decode_step", broken)
    cell, run, params, window = serve(seconds=1.0)
    v = check(cell, params, window)
    assert not v.ok
    assert v.checks["mean_logit_gap"]["value"] > LIMIT["mean_logit_gap"]


def test_altered_token_fails_correct(monkeypatch):
    _served_with_fault(_altered_token, monkeypatch)


def test_unchanged_cache_fails_correct(monkeypatch):
    _served_with_fault(_cache_unchanged, monkeypatch)


def test_sample_always_holds_the_longest(closed_forest):
    cell, run, params, window = closed_forest
    done = correct.finished(window)
    longest = max(len(s.prompt) + len(s.tokens) for s in done)
    for seed in (1, 2, 3):
        picked = correct.sample(window, seed, 3)
        assert len(picked) == min(3, len(done))
        assert len(picked[0].prompt) + len(picked[0].tokens) == longest
        assert len({s.rid for s in picked}) == len(picked)
    np.testing.assert_array_equal(
        [s.rid for s in correct.sample(window, 7, 3)],
        [s.rid for s in correct.sample(window, 7, 3)])


def test_traced_run_reads_the_window_from_the_trace():
    """``--trace 1``'s path: the profiler runs over the window, its file is
    read back and reduced, and the window is found by its host span.  The
    CPU has no device plane, so no op is busy and the kernel readers find
    nothing to read."""
    cell, cfg = tiny.cell(1, "closed", LIMIT)
    run, params, window = bench_run.serve(cell, cfg, tiny.PEAKS, SEED, 0.5,
                                          True, jax.devices())
    assert run.trace is not None
    assert run.trace.window_s == pytest.approx(
        window.t_close - window.t_open, rel=0.5)
    names = {n for n, *_ in run.trace_table["host"]}
    assert {"bench.window", "engine.step"} <= names
    assert bench_run.read_metric("fff_fused_decode_roofline", run) is None
    assert bench_run.read_metric("idle_share.backlog", run) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("traced, mix, want", [
    (False, {}, 45.0), (True, {}, 45.0), (False, {"trace_seconds": 5}, 45.0),
    (True, {"trace_seconds": 5}, 5.0), (True, {"trace_seconds": 60}, 45.0)])
def test_trace_seconds_shortens_only_a_traced_window(traced, mix, want):
    assert bench_run.window_seconds(mix, 45.0, traced) == want
