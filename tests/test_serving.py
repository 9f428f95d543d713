"""Continuous-batching serving engine tests (DESIGN.md §9).

Three tiers, mirroring tests/test_grouped_ep.py:
* host-only scheduler property tests — admission policy is pure numpy;
* engine tests on the reduced config — slot lifecycle, parity with
  ``lm.generate``, EOS handling, the fixed-compiled-shape contract,
  determinism under seed, and the leaf-aware-beats-FCFS overflow claim;
* a subprocess tier driving ``launch/serve.py --engine continuous`` under a
  ``--model-parallel`` mesh with the ``grouped_ep`` backend (8 fake host
  devices, like tests/test_sharding.py).
"""
import ast
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import api
from repro.models import lm
from repro.serving import (ContinuousBatchingEngine, EngineConfig, Request,
                           make_scheduler)
from repro.serving.metrics import summarize
from repro.serving.scheduler import SchedulerView

from test_sharding import run_with_fake_devices


# ---------------------------------------------------------------------------
# host-only tier: scheduler properties
# ---------------------------------------------------------------------------

def _view(num_slots=8, E=4, occupancy=None, active=None, cf=2.0):
    return SchedulerView(
        occupancy=(occupancy if occupancy is not None
                   else np.zeros((num_slots, E))),
        active=(active if active is not None
                else np.zeros((num_slots,), bool)),
        num_leaves=E, capacity_factor=cf, num_slots=num_slots)


def _req(rid, hint=None, L=4):
    return Request(rid=rid, prompt=np.ones((L,), np.int32),
                   max_new_tokens=4, leaf_hint=hint)


def test_fcfs_is_arrival_order():
    s = make_scheduler("fcfs")
    ws = [_req(i) for i in range(5)]
    assert [r.rid for r in s.select(ws, 3, _view())] == [0, 1, 2]


def test_leaf_aware_balances_classes():
    """With current load all on leaf 0 and candidates split by leaf, the
    scheduler admits the complementary class first."""
    E = 4
    occ = np.zeros((8, E))
    occ[0] = occ[1] = [1.0, 0, 0, 0]
    active = np.zeros((8,), bool)
    active[:2] = True
    hot = np.array([1.0, 0, 0, 0])
    cold = np.array([0, 1.0, 0, 0])
    s = make_scheduler("leaf_aware")
    ws = [_req(0, hot), _req(1, hot), _req(2, cold)]
    # capacity proxy is generous at these sizes; force pressure via cf
    view = _view(num_slots=8, E=E, occupancy=occ, active=active, cf=0.01)
    got = s.select(ws, 1, view)
    assert [r.rid for r in got] == [2]


def test_leaf_aware_no_starvation():
    """An adversarial stream that always offers a better-balancing candidate
    must still admit the queue head within max_hold rounds."""
    E = 2
    hot = np.array([1.0, 0.0])
    cold = np.array([0.0, 1.0])
    s = make_scheduler("leaf_aware", window=8, max_hold=3)
    occ = np.zeros((4, E))
    occ[0] = hot
    active = np.zeros((4,), bool)
    active[0] = True
    view = _view(num_slots=4, E=E, occupancy=occ, active=active, cf=0.01)
    waiting = [_req(0, hot)] + [_req(100 + i, cold) for i in range(20)]
    rounds = 0
    while waiting and rounds < 20:
        got = s.select(waiting, 1, view)
        assert got, "scheduler must admit when a slot is free"
        waiting.remove(got[0])
        rounds += 1
        if got[0].rid == 0:
            break
    assert rounds <= 4, f"head starved for {rounds} rounds"


def test_leaf_aware_deterministic():
    rng = np.random.default_rng(0)
    ws = [_req(i, rng.dirichlet(np.ones(4))) for i in range(12)]
    picks = []
    for _ in range(2):
        s = make_scheduler("leaf_aware")
        picks.append([r.rid for r in s.select(list(ws), 6, _view(E=4))])
    assert picks[0] == picks[1]


def test_leaf_aware_degrades_without_telemetry():
    s = make_scheduler("leaf_aware")
    ws = [_req(i) for i in range(4)]
    got = s.select(ws, 2, _view(E=0))
    assert [r.rid for r in got] == [0, 1]


def test_metrics_percentiles():
    m = summarize([0.001] * 90 + [0.101] * 10)
    assert m.p50_ms == pytest.approx(1.0)
    assert m.p99_ms == pytest.approx(101.0)
    assert m.p90_ms <= m.p99_ms <= m.max_ms
    assert m.n == 100


# ---------------------------------------------------------------------------
# engine tier (reduced config, single device)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = registry.get_config("internlm2-20b", ffn="fff").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    defaults = dict(num_slots=4, max_len=48, max_prompt_len=16, seed=0)
    defaults.update(kw)
    return ContinuousBatchingEngine(params, cfg, EngineConfig(**defaults))


def _mixed_requests(n, rng, max_new=6, eos=None):
    return [Request(rid=i,
                    prompt=rng.integers(1, 256, int(rng.integers(3, 17))),
                    max_new_tokens=max_new + int(rng.integers(0, 3)),
                    eos_id=eos)
            for i in range(n)]


def test_engine_serves_all_no_slot_leak(model):
    cfg, params = model
    eng = _engine(cfg, params)
    reqs = _mixed_requests(9, np.random.default_rng(1))
    results, m = eng.run(reqs)
    assert sorted(r.rid for r in results) == list(range(9))
    assert all(s is None for s in eng.slots), "slot leak"
    assert not eng.queue
    assert m.n_requests == 9
    assert all(r.n_generated == reqs[r.rid].max_new_tokens for r in results)
    assert all(r.finish_reason == "length" for r in results)
    assert m.n_tokens == sum(r.n_generated for r in results)


def test_engine_matches_lm_generate(model):
    """Greedy engine output must equal the synchronous lm.generate path for
    every request, whatever batch composition it decoded in (exact per-token
    backends; DESIGN.md §9)."""
    cfg, params = model
    eng = _engine(cfg, params)
    results, _ = eng.run(_mixed_requests(6, np.random.default_rng(2)))
    for r in results:
        want = lm.generate(params, cfg, jnp.asarray(r.prompt[None]),
                           steps=r.n_generated, max_len=48)
        np.testing.assert_array_equal(
            np.asarray(want)[0], np.concatenate([r.prompt, r.tokens]),
            err_msg=f"rid {r.rid}")


def test_engine_eos_stops_early(model):
    cfg, params = model
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 256, 8)
    base = lm.generate(params, cfg, jnp.asarray(prompt[None]), steps=8,
                       max_len=48)
    eos = int(np.asarray(base)[0, len(prompt) + 2])     # 3rd generated token
    eng = _engine(cfg, params)
    results, _ = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=8,
                                  eos_id=eos)])
    r = results[0]
    assert r.finish_reason == "eos"
    assert r.n_generated == 3
    assert r.tokens[-1] == eos


def test_generate_eos_stops_early(model):
    cfg, params = model
    prompt = jnp.asarray(np.random.default_rng(3).integers(1, 256, (1, 8)))
    base = np.asarray(lm.generate(params, cfg, prompt, steps=8, max_len=48))
    eos = int(base[0, 8 + 2])
    out = np.asarray(lm.generate(params, cfg, prompt, steps=8, max_len=48,
                                 eos_id=eos))
    assert out.shape[1] == 8 + 3                       # stopped, not padded on
    np.testing.assert_array_equal(out[0], base[0, :11])


def test_engine_fixed_compiled_shapes(model):
    """The pad-to-slot contract: serving two waves of mixed-length requests
    compiles exactly one decode shape and at most one shape per prefill
    bucket — no per-step retracing after warmup."""
    cfg, params = model
    eng = _engine(cfg, params, max_prompt_len=16, prefill_buckets=(8, 16))
    eng.run(_mixed_requests(5, np.random.default_rng(4)))
    warm = eng.compiled_shapes()
    eng.run(_mixed_requests(7, np.random.default_rng(5)))
    after = eng.compiled_shapes()
    assert after == warm, "recompilation after warmup"
    assert after["decode"] == 1
    assert after["admit"] == 1
    assert all(v <= 1 for k, v in after.items() if k.startswith("prefill_"))
    assert sum(v for k, v in after.items() if k.startswith("prefill_")) >= 1


def test_engine_deterministic_sampling(model):
    cfg, params = model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, 7) for _ in range(4)]

    def run():
        eng = _engine(cfg, params)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5, temperature=0.8)
                for i, p in enumerate(prompts)]
        results, _ = eng.run(reqs)
        return [r.tokens.tolist() for r in results]

    assert run() == run()


def test_leaf_aware_cuts_overflow_vs_fcfs(model):
    """The acceptance claim, miniaturized from benchmarks/serving_load.py:
    on a skewed-routing workload under the capacity-bounded grouped backend,
    leaf-aware admission yields strictly lower decode overflow_fraction than
    FCFS at the same decode-step count."""
    cfg, params = model
    from benchmarks.serving_load import calibrate_classes
    classes = calibrate_classes(params, cfg, 2)
    slots = 16

    def run(sched):
        kw = {"window": 4 * slots} if sched == "leaf_aware" else {}
        eng = ContinuousBatchingEngine(params, cfg, EngineConfig(
            num_slots=slots, max_len=24, max_prompt_len=16, scheduler=sched,
            scheduler_kw=kw, fff_backend="grouped",
            max_prefills_per_step=slots, seed=0))
        reqs = []
        for burst in range(2):
            tok, fp = classes[burst % 2]
            for i in range(slots):
                reqs.append(Request(rid=burst * slots + i,
                                    prompt=np.full(16, tok, np.int32),
                                    max_new_tokens=6, leaf_hint=fp.copy()))
        _, m = eng.run(reqs)
        return m

    m_fcfs = run("fcfs")
    m_aware = run("leaf_aware")
    assert m_aware.n_steps == m_fcfs.n_steps       # same decode work
    assert m_fcfs.overflow_decode_mean > 0.2       # the bound actually bites
    assert m_aware.overflow_decode_mean < m_fcfs.overflow_decode_mean


def test_engine_rejects_unservable_requests(model):
    cfg, params = model
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="bucket"):
        eng.submit(Request(rid=0, prompt=np.ones(17, np.int32)))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(rid=1, prompt=np.ones(16, np.int32),
                           max_new_tokens=40))
    eng.submit(Request(rid=2, prompt=np.ones(4, np.int32), max_new_tokens=1))
    with pytest.raises(ValueError, match="rid"):
        eng.submit(Request(rid=2, prompt=np.ones(4, np.int32),
                           max_new_tokens=1))
    with pytest.raises(ValueError, match="duplicate"):
        eng.run([Request(rid=7, prompt=np.ones(4, np.int32)),
                 Request(rid=7, prompt=np.ones(4, np.int32))])


def test_engine_run_replays_request_lists(model):
    """run() must not mutate caller arrival offsets: the same Request list
    replays on a warm engine with sane per-wave TTFT."""
    cfg, params = model
    eng = _engine(cfg, params)
    reqs = [Request(rid=i, prompt=np.full(5, 3, np.int32), max_new_tokens=3)
            for i in range(2)]
    r1, m1 = eng.run(reqs)
    assert all(r.arrival_time == 0.0 for r in reqs)    # offsets untouched
    r2, m2 = eng.run(reqs)
    assert [r.rid for r in r2] == [0, 1]
    assert r1[0].tokens.tolist() == r2[0].tokens.tolist()
    # warm-wave TTFT is fresh, not inflated by wave 1's duration
    assert m2.ttft.max_ms <= m1.ttft.max_ms


def test_engine_rejects_recurrent_mixers():
    cfg = registry.get_config("xlstm-1.3b", ffn="fff").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attention"):
        ContinuousBatchingEngine(params, cfg, EngineConfig(
            num_slots=2, max_len=32, max_prompt_len=16))


def test_routing_tap_is_scoped(model):
    """No tap, no telemetry: decode_step must not return routing stats (and
    the train path is unaffected by an active tap)."""
    cfg, params = model
    caches = lm.init_caches(cfg, 1, 8)
    tok = jnp.ones((1, 1), jnp.int32)
    out = lm.decode_step(params, cfg, tok, caches, 0, with_stats=True)
    assert out[2] is None
    with api.collect_routing():
        out = lm.decode_step(params, cfg, tok, caches, 0, with_stats=True)
    assert out[2] is not None and any(s is not None for s in out[2])


# ---------------------------------------------------------------------------
# subprocess tier: engine e2e under the expert-parallel mesh
# ---------------------------------------------------------------------------

def test_engine_e2e_model_parallel_grouped_ep():
    """serve --engine continuous --scheduler leaf_aware --model-parallel 4
    --fff-backend grouped_ep: the engine loop traces under the (data, model)
    mesh and exchanges tokens over the model axis.  The pool pinned under
    the mesh keeps its placement through every call, so each entry point
    compiles once."""
    code = textwrap.dedent("""
        import sys
        sys.argv = ["serve", "--arch", "internlm2-20b", "--reduced",
                    "--engine", "continuous", "--scheduler", "leaf_aware",
                    "--batch", "4", "--requests", "6", "--prompt-len", "16",
                    "--gen", "3", "--fff-backend", "grouped_ep",
                    "--model-parallel", "4", "--prefill-chunk", "8"]
        from repro.launch import serve
        serve.main()
        from repro.serving import tracing
        rows = tracing.samples(tracing.SLAB_ROWS, 0.0, float("inf"))
        print("slab rows:", sorted({int(r) for r in rows}))
    """)
    out = run_with_fake_devices(code)
    assert "expert-parallel serving" in out
    assert "served 6 requests" in out
    assert "decode step" in out
    shapes = ast.literal_eval(
        out.split("compiled shapes: ", 1)[1].splitlines()[0])
    reached = ast.literal_eval(out.split("slab rows: ", 1)[1].splitlines()[0])
    family = {f"prefill_chunk_{r}": 1 for r in (1, 2, 4)}    # --batch 4
    assert (shapes["decode"], shapes["admit"]) == (1, 1), shapes
    assert {k: v for k, v in shapes.items()
            if k.startswith("prefill_chunk_")} == family, shapes
    assert reached and {f"prefill_chunk_{r}" for r in reached} <= \
        set(family), reached


def test_serve_cli_no_reduced_means_published_widths():
    from repro.launch import serve
    assert serve.parse_args([]).reduced is True
    assert serve.parse_args(["--reduced"]).reduced is True
    assert serve.parse_args(["--no-reduced"]).reduced is False
