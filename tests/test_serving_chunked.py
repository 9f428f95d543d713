"""Chunked-prefill invariants (ISSUE 4 tentpole; DESIGN.md §9).

* logits parity: a prompt prefetched chunk-by-chunk (``lm.prefill_chunk``)
  must produce the same next-token logits and cache state as one monolithic
  padded prefill (``lm.prefill_padded``);
* telemetry accumulation: per-slot FFF leaf counts summed across a
  request's chunks equal the monolithic prefill's counts;
* no decode starvation: short requests keep producing tokens while a
  continuous stream of long prompts is admitted;
* the fixed-compiled-shape bound: chunked serving compiles exactly one
  decode shape and one shape per chunk row bucket, whatever the workload
  mix;
* row compaction: a slab of only the prefilling rows matches the full
  ``(num_slots, C)`` slab on those rows and leaves every other row
  bit-identical;
* engine-level token parity with ``lm.generate``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import api
from repro.models import lm
from repro.serving import (ContinuousBatchingEngine, EngineConfig, Request,
                           make_scheduler, tracing)
from repro.serving.scheduler import SchedulerView


@pytest.fixture(scope="module")
def model():
    cfg = registry.get_config("internlm2-20b", ffn="fff").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _chunked_prefill(params, cfg, prompt, caches, slot, num_slots, chunk,
                     free_fill=1):
    """Drive lm.prefill_chunk over one prompt at ``slot``; other rows idle.
    Returns (final logits row, caches, accumulated (E,) leaf counts)."""
    E = 2 ** next(b.ffn.fff_depth for b in cfg.period if b.ffn.kind == "fff")
    counts = np.zeros((E,))
    pos, logits = 0, None
    while pos < len(prompt):
        n = min(chunk, len(prompt) - pos)
        slab = np.full((num_slots, chunk), free_fill, np.int32)
        slab[slot, :n] = prompt[pos:pos + n]
        slab[slot, n:] = prompt[pos + n - 1]
        valid = np.zeros((num_slots,), np.int32)
        valid[slot] = n
        offs = np.zeros((num_slots,), np.int32)
        offs[slot] = pos
        with api.collect_routing():
            lg, caches, stats = jax.jit(
                lambda p, t, v, c, o: lm.prefill_chunk(p, cfg, t, v, c, o)
            )(params, jnp.asarray(slab), jnp.asarray(valid), caches,
              jnp.asarray(offs))
        for s in (stats or ()):
            if s is not None and s.leaf_counts.shape[-1] == E:
                counts += np.asarray(s.leaf_counts)[slot]
        pos += n
        logits = np.asarray(lg)[slot]
    return logits, caches, counts


def _monolithic_prefill(params, cfg, prompt, caches, num_slots):
    """Padded prefill of ``prompt`` in row 1 of a (num_slots, L) batch,
    with accumulated (E,) leaf counts for that row."""
    E = 2 ** next(b.ffn.fff_depth for b in cfg.period if b.ffn.kind == "fff")
    L = len(prompt)
    toks = np.ones((num_slots, L), np.int32)
    toks[1] = prompt
    true_len = np.ones((num_slots,), np.int32)
    true_len[1] = L
    with api.collect_routing():
        logits, caches, stats = jax.jit(
            lambda p, t, c, n: lm.prefill_padded(p, cfg, {"tokens": t}, c, n)
        )(params, jnp.asarray(toks), caches, jnp.asarray(true_len))
    counts = np.zeros((E,))
    for s in (stats or ()):
        if s is not None and s.leaf_counts.shape[-1] == E:
            counts += np.asarray(s.leaf_counts)[1]
    return np.asarray(logits)[1], caches, counts


# ---------------------------------------------------------------------------
# model-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,chunk", [(16, 8), (16, 4), (12, 8)])
def test_chunked_matches_monolithic_logits(model, L, chunk):
    """Same prompt, same final logits and same decode continuation whether
    prefilled in one padded dispatch or chunk-by-chunk (incl. a final
    partial chunk for L=12, chunk=8)."""
    cfg, params = model
    B, max_len = 4, 32
    prompt = np.random.default_rng(0).integers(1, 256, L).astype(np.int32)

    lg_m, caches_m, _ = _monolithic_prefill(
        params, cfg, prompt, lm.init_caches(cfg, B, max_len), B)
    lg_c, caches_c, _ = _chunked_prefill(
        params, cfg, prompt, lm.init_caches(cfg, B, max_len), 1, B, chunk)
    np.testing.assert_allclose(lg_c, lg_m, rtol=2e-4, atol=2e-4)

    # the caches must be interchangeable: decode the argmax token on both
    tok = np.zeros((B, 1), np.int32)
    tok[1, 0] = lg_m.argmax()
    lm_m, _ = lm.decode_step(params, cfg, jnp.asarray(tok), caches_m, 0)
    lm_c, _ = lm.decode_step(params, cfg, jnp.asarray(tok), caches_c, 0)
    np.testing.assert_allclose(np.asarray(lm_c)[1], np.asarray(lm_m)[1],
                               rtol=2e-4, atol=2e-4)
    # and agree on the cache's filled length for the active row
    np.testing.assert_array_equal(
        np.asarray(caches_m[0]["kv"].length)[:, 1],
        np.asarray(caches_c[0]["kv"].length)[:, 1])


def test_chunked_telemetry_accumulates_to_monolithic(model):
    """Summing a request's per-chunk leaf counts reproduces the monolithic
    prefill's counts (no pad anywhere: L divides into whole chunks and
    equals the bucket)."""
    cfg, params = model
    B, L, chunk, max_len = 4, 16, 8, 32
    prompt = np.random.default_rng(1).integers(1, 256, L).astype(np.int32)
    _, _, c_mono = _monolithic_prefill(
        params, cfg, prompt, lm.init_caches(cfg, B, max_len), B)
    _, _, c_chunk = _chunked_prefill(
        params, cfg, prompt, lm.init_caches(cfg, B, max_len), 1, B, chunk)
    # counts are integers (routed slots); fp noise in hidden states may
    # flip a borderline token's leaf, so allow a one-slot wobble per leaf
    np.testing.assert_allclose(c_chunk, c_mono, atol=1)
    assert c_chunk.sum() == c_mono.sum()          # every slot accounted for


def test_inactive_rows_untouched(model):
    """A chunk dispatch must not perturb rows with valid_len == 0: a decode
    on an unrelated slot yields identical logits before and after."""
    cfg, params = model
    B, max_len = 4, 32
    prompt = np.random.default_rng(2).integers(1, 256, 16).astype(np.int32)
    caches = lm.init_caches(cfg, B, max_len)
    # occupy row 0 with a short monolithic prefill
    toks = np.tile(prompt[:8][None], (B, 1))
    tl = np.ones((B,), np.int32)
    tl[0] = 8
    _, caches, _ = lm.prefill_padded(params, cfg,
                                     {"tokens": jnp.asarray(toks)}, caches,
                                     jnp.asarray(tl))
    tok = np.full((B, 1), 7, np.int32)
    probe = lambda c: np.asarray(lm.decode_step(
        params, cfg, jnp.asarray(tok), c, 0)[0])[0]
    before = probe(caches)
    # now chunk-prefill row 2; row 0 must be bit-identical afterwards
    _, caches, _ = _chunked_prefill(params, cfg, prompt, caches, 2, B, 8)
    np.testing.assert_array_equal(probe(caches), before)


# ---------------------------------------------------------------------------
# row compaction: a slab of only the prefilling rows
# ---------------------------------------------------------------------------

def _pool_pages(caches, table_row):
    """Every layer's K and V pages of one cache row's page table."""
    return [np.asarray(a)[:, table_row] for c in caches
            for a in (c["kv"].k, c["kv"].v)]


@pytest.mark.parametrize("slots", [[2], [3, 1], [0, 2, 3], [1, 0, 3, 2]],
                         ids=["1row", "2rows", "3rows", "num_slots"])
def test_compacted_slab_matches_full_slab(model, slots):
    """``lm.prefill_chunk`` over a slab of only the prefilling rows (in any
    order, padded with out-of-range rows to a power of two) matches the full
    ``(num_slots, C)`` slab: the same logits on those rows, the same pages
    and lengths for them, and every other row's pages and length — and
    every page no row owns, which a padding row could hit — bit-identical."""
    cfg, params = model
    B, C, max_len, page = 4, 8, 32, 8
    rng = np.random.default_rng(11)
    caches = lm.init_caches(cfg, B, max_len, page_size=page,
                            num_pages=B * (max_len // page) + 3)
    # a history of different lengths in every row
    hist = np.asarray([5, 8, 3, 6], np.int32)
    _, caches, _ = lm.prefill_chunk(
        params, cfg, jnp.asarray(rng.integers(1, 256, (B, C)), jnp.int32),
        jnp.asarray(hist), caches, jnp.zeros((B,), jnp.int32))

    n = {i: int(v) for i, v in zip(slots, rng.integers(1, C + 1, B))}
    toks = rng.integers(1, 256, (B, C)).astype(np.int32)
    valid = np.zeros((B,), np.int32)
    offs = np.zeros((B,), np.int32)
    for i in slots:
        valid[i], offs[i] = n[i], hist[i]
    lg_full, full, _ = jax.jit(
        lambda c: lm.prefill_chunk(params, cfg, jnp.asarray(toks),
                                   jnp.asarray(valid), c, jnp.asarray(offs))
    )(caches)

    R = 1 << (len(slots) - 1).bit_length()
    rows = np.full((R,), B, np.int32)                   # padding: row B
    rows[:len(slots)] = slots
    pick = np.minimum(rows, B - 1)
    lg_rows, compact, _ = jax.jit(
        lambda c: lm.prefill_chunk(
            params, cfg, jnp.asarray(toks[pick]),
            jnp.asarray(np.where(rows < B, valid[pick], 0)), c,
            jnp.asarray(offs[pick]), jnp.asarray(rows))
    )(caches)

    assert np.asarray(lg_rows).shape == (R, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(lg_rows)[:len(slots)],
                               np.asarray(lg_full)[slots], rtol=1e-5,
                               atol=1e-5)
    table = np.asarray(caches[0]["kv"].table)[0]
    for i in range(B):
        got, want, before = (_pool_pages(c, table[i])
                             for c in (compact, full, caches))
        for g, w, b in zip(got, want, before):
            if i in slots:
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
            else:
                np.testing.assert_array_equal(g, w)
                np.testing.assert_array_equal(g, b)
    spare = np.setdiff1d(np.arange(caches[0]["kv"].k.shape[1]), table)
    assert spare.size
    for g, b in zip(_pool_pages(compact, spare), _pool_pages(caches, spare)):
        np.testing.assert_array_equal(g, b)
    for c, f in zip(compact, full):
        np.testing.assert_array_equal(np.asarray(c["kv"].length),
                                      np.asarray(f["kv"].length))
        np.testing.assert_array_equal(np.asarray(c["kv"].table),
                                      np.asarray(f["kv"].table))
    want_len = hist + valid
    np.testing.assert_array_equal(np.asarray(compact[0]["kv"].length)[0],
                                  want_len)


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "spec"])
def test_engine_three_of_four_slots_prefilling_matches_generate(model,
                                                                spec_k):
    """Three prompts admitted in one step prefill together through a
    4-row slab with one padding row; every greedy token equals
    ``lm.generate``'s, with speculation off and on."""
    cfg, params = model
    eng = _engine(cfg, params, max_prefills_per_step=3, spec_k=spec_k)
    reached = _track_slab_rows(eng)
    rng = np.random.default_rng(12)
    reqs = [Request(rid=i, prompt=rng.integers(1, 256, int(L)),
                    max_new_tokens=5)
            for i, L in enumerate((40, 33, 50))]
    results, m = eng.run(reqs)
    assert reached.n_max == 3 and 4 in reached
    for r in results:
        want = lm.generate(params, cfg, jnp.asarray(r.prompt[None]),
                           steps=r.n_generated, max_len=80)
        np.testing.assert_array_equal(
            np.asarray(want)[0], np.concatenate([r.prompt, r.tokens]),
            err_msg=f"rid {r.rid}")


@pytest.mark.parametrize("policy,compact",
                         [("drop", False), ("master_leaf", False),
                          ("exact_dense", True)])
def test_slab_rows_follow_the_overflow_policy(model, policy, compact):
    """Under a capacity-bounded dispatch that drops (``drop``) or stands in
    (``master_leaf``) per-leaf capacity scales with the slab, so the slab
    stays ``(num_slots, C)``; under the exact repair it holds only the
    prefilling rows.  The counter ``prefill.slab_rows`` reads the rows of
    every slab."""
    cfg, _ = model
    cfg = dataclasses.replace(cfg, period=tuple(
        dataclasses.replace(b, ffn=dataclasses.replace(
            b.ffn, fff_master_leaf=True)) for b in cfg.period))
    params = lm.init(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params, fff_backend="grouped", overflow_policy=policy)
    reached = _track_slab_rows(eng)
    rng = np.random.default_rng(13)
    eng.run([Request(rid=i, prompt=rng.integers(1, 256, 40),
                     max_new_tokens=2) for i in range(2)])
    assert reached == {2} and reached.n_max == 2
    family = {1, 2, 4} if compact else {4}
    assert _chunk_buckets(eng.compiled_shapes()) == {r: 1 for r in family}
    got = tracing.samples(tracing.SLAB_ROWS, 0.0, float("inf"),
                          engine=eng._trace_id)
    assert got and set(got) == ({2.0} if compact else {4.0})


# ---------------------------------------------------------------------------
# engine-level invariants
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    defaults = dict(num_slots=4, max_len=80, max_prompt_len=64,
                    prefill_chunk=16, prefill_budget=1, seed=0)
    defaults.update(kw)
    return ContinuousBatchingEngine(params, cfg, EngineConfig(**defaults))


def _mixed_requests(n, rng, max_new=6):
    return [Request(rid=i,
                    prompt=rng.integers(1, 256, int(rng.integers(3, 50))),
                    max_new_tokens=max_new + int(rng.integers(0, 3)))
            for i in range(n)]


def test_chunked_engine_matches_lm_generate(model):
    """Greedy chunked-engine output equals the synchronous lm.generate path
    for every request (the monolithic-engine parity test, chunked)."""
    cfg, params = model
    eng = _engine(cfg, params)
    results, m = eng.run(_mixed_requests(7, np.random.default_rng(3)))
    assert m.n_chunks > 0
    for r in results:
        want = lm.generate(params, cfg, jnp.asarray(r.prompt[None]),
                           steps=r.n_generated, max_len=80)
        np.testing.assert_array_equal(
            np.asarray(want)[0], np.concatenate([r.prompt, r.tokens]),
            err_msg=f"rid {r.rid}")


class _Reached(set):
    """Row buckets reached, and the most slots that prefilled at once."""
    n_max = 0


def _track_slab_rows(eng) -> _Reached:
    """The chunk row buckets ``eng``'s slabs need from now on, worked out
    from the prefilling slots before each slab dispatch (the next power of
    two, capped at num_slots) — independently of the engine's own bucket
    choice."""
    reached = _Reached()
    inner = eng._chunk_prefill

    def chunk_prefill():
        n = sum(s is not None and s.prefilling for s in eng.slots)
        if n:
            reached.add(min(1 << (n - 1).bit_length(), eng.ecfg.num_slots))
            reached.n_max = max(reached.n_max, n)
        inner()

    eng._chunk_prefill = chunk_prefill
    return reached


def _chunk_buckets(shapes) -> dict:
    """Row bucket -> compiled traces, from ``compiled_shapes``."""
    return {int(k.rsplit("_", 1)[1]): v for k, v in shapes.items()
            if k.startswith("prefill_chunk_")}


def test_chunked_fixed_compiled_shapes(model):
    """Chunked serving compiles ONE decode shape, each chunk row bucket
    exactly once (all at the first slab) and ZERO prefill buckets, whatever
    the prompt-length mix — tighter than the monolithic per-bucket bound."""
    cfg, params = model
    eng = _engine(cfg, params)
    reached = _track_slab_rows(eng)
    eng.run(_mixed_requests(6, np.random.default_rng(4)))
    warm = eng.compiled_shapes()
    eng.run(_mixed_requests(8, np.random.default_rng(5)))
    after = eng.compiled_shapes()
    assert after == warm, "recompilation after warmup"
    assert after["decode"] == 1
    assert _chunk_buckets(after) == {1: 1, 2: 1, 4: 1}
    assert reached and reached <= set(_chunk_buckets(after))
    assert all(v == 0 for k, v in after.items() if k.startswith("prefill_")
               and not k.startswith("prefill_chunk_"))


def test_no_decode_starvation_under_long_prompt_stream(model):
    """While a continuous stream of max-length prompts is admitted, an
    in-flight short request must keep producing tokens: with chunk c over
    prompt L the admission spans ~L/c steps and the short request gets a
    decode in each — under monolithic prefill it would finish no earlier
    than the long prompt's first token."""
    cfg, params = model
    eng = _engine(cfg, params, num_slots=2, prefill_chunk=8)
    rng = np.random.default_rng(6)
    short = Request(rid=0, prompt=rng.integers(1, 256, 4),
                    max_new_tokens=6)
    eng.submit(short)
    eng.step()                                    # short admitted + decoding
    for j in range(3):                            # long-prompt stream
        eng.submit(Request(rid=1 + j, prompt=rng.integers(1, 256, 64),
                           max_new_tokens=1))
    first_long_done = None
    steps = 0
    while eng.has_work() and steps < 200:
        eng.step()
        steps += 1
        if first_long_done is None and any(
                r.rid == 1 for r in eng.results):
            first_long_done = steps
    # 64-token prompts over 8-token chunks: >= 8 steps of admission per
    # long request; the short request (6 tokens) must have finished while
    # the FIRST long prompt was still prefilling
    short_res = next(r for r in eng.results if r.rid == 0)
    long_res = next(r for r in eng.results if r.rid == 1)
    assert short_res.finish_time < long_res.first_token_time, \
        "short request was starved by long-prompt admission"
    assert short_res.n_generated == 6


def test_scheduler_max_prefilling_caps_admission(model):
    """The scheduler-side TTFT-vs-p99 knob: with max_prefilling=1 the
    engine never holds two slots mid-prefill at once."""
    cfg, params = model
    eng = _engine(cfg, params, num_slots=4, prefill_chunk=16,
                  scheduler_kw={"max_prefilling": 1})
    rng = np.random.default_rng(7)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=rng.integers(1, 256, 64),
                           max_new_tokens=1))
    max_seen = 0
    steps = 0
    while eng.has_work() and steps < 300:
        eng.step()
        steps += 1
        max_seen = max(max_seen, sum(
            s is not None and s.prefilling for s in eng.slots))
    assert len(eng.results) == 4
    assert max_seen <= 1, f"{max_seen} slots mid-prefill despite cap"


def test_scheduler_admission_cap_math():
    view = SchedulerView(occupancy=np.zeros((4, 2)),
                         active=np.zeros((4,), bool), num_leaves=2,
                         capacity_factor=2.0, num_slots=4,
                         prefilling=np.asarray([True, True, False, False]))
    assert make_scheduler("fcfs").admission_cap(view) == 4     # uncapped
    assert make_scheduler("fcfs", max_prefilling=3).admission_cap(view) == 1
    assert make_scheduler("leaf_aware",
                          max_prefilling=2).admission_cap(view) == 0


def test_compile_bound(model):
    """The documented compile contract, standalone: chunked serving runs on
    EXACTLY decode 1 + admit 1 + each chunk row bucket 1 compiled traces
    with zero bucket prefills (docs/serving.md).  The CI
    serving job runs this single node id as a dedicated gate step, so a
    contract regression fails loudly on its own instead of somewhere inside
    the full suite."""
    cfg, params = model
    eng = _engine(cfg, params)
    eng.run(_mixed_requests(5, np.random.default_rng(9)))
    shapes = eng.compiled_shapes()
    assert shapes["decode"] == 1, shapes
    assert _chunk_buckets(shapes) == {1: 1, 2: 1, 4: 1}, shapes
    assert shapes["admit"] == 1, shapes
    assert all(v == 0 for k, v in shapes.items()
               if k.startswith("prefill_")
               and not k.startswith("prefill_chunk_")), shapes


def test_chunk_config_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="power of two"):
        _engine(cfg, params, prefill_chunk=12)
    with pytest.raises(ValueError, match="max_prompt_len"):
        _engine(cfg, params, prefill_chunk=128, max_prompt_len=64)
    with pytest.raises(ValueError, match="prefill_budget"):
        _engine(cfg, params, prefill_budget=0)


def test_poll_metrics_snapshot(model):
    """poll_metrics reports live queue/slot state mid-run and zeroes out
    once drained."""
    cfg, params = model
    eng = _engine(cfg, params, num_slots=2, prefill_chunk=8)
    rng = np.random.default_rng(8)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=rng.integers(1, 256, 32),
                           max_new_tokens=2))
    eng.step()
    m = eng.poll_metrics()
    assert m.active_slots == 2 and m.prefilling_slots >= 1
    assert m.queue_depth == 4 - m.active_slots
    assert m.n_chunks >= 1
    while eng.has_work():
        eng.step()
    m = eng.poll_metrics()
    assert m.queue_depth == 0 and m.active_slots == 0
    assert m.n_requests == 4
    assert {"queue_depth", "decode_interval_ms", "n_chunks"} <= set(
        m.as_dict())
