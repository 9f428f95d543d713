"""The serving engine's spans and counters (``serving/tracing.py``).

* spans are plain ``jax.profiler.TraceAnnotation``s: they cost nothing
  visible and leave nothing behind unless a profiler session runs;
* ``fff.decode_leaves`` is exact on planted routing: every row on one leaf
  reads 1, and a known spread over a 2-layer scanned model reads the mean
  over the sites, phantom rows (free slots) left out;
* ``engine.recompiles`` stays 0 through steady serving and counts a forced
  new shape;
* ``EngineMetrics``, ``poll_metrics()`` and ``serve.py --metrics-json``
  carry both counters.
"""
import dataclasses
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import api
from repro.models import lm
from repro.serving import ContinuousBatchingEngine, EngineConfig, Request
from repro.serving import tracing

PLANTED = "planted_routing"


def _tiny_cfg(n_layers=2, scan=True):
    cfg = registry.get_config("internlm2-20b", ffn="fff").reduced(
        n_layers=n_layers, d_model=64, n_heads=4, n_kv_heads=2, vocab=128,
        seq=64)
    return dataclasses.replace(cfg, scan_layers=scan)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    return cfg, lm.init(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(num_slots=4, max_len=48, max_prompt_len=16, **kw)
    return ContinuousBatchingEngine(params, cfg, ecfg)


def _requests(n, rid0=0, prompt_len=6, gen=6):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(1, 128, prompt_len, dtype=np.int32),
                    max_new_tokens=gen) for i in range(n)]


def _serve(engine, reqs) -> int:
    """Serve ``reqs`` to completion; returns the number of steps."""
    for r in reqs:
        engine.submit(r)
    n = 0
    while engine.has_work():
        engine.step()
        n += 1
    return n


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _host_event_names(logdir):
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in pd.planes for line in plane.lines
            for ev in line.events}


def test_spans_are_no_ops_without_a_profiler(tiny, tmp_path):
    """Steps served with no profiler running leave no span in a session
    started afterwards; a step served inside a session leaves the tree."""
    cfg, params = tiny
    assert isinstance(tracing.span(tracing.STEP),
                      jax.profiler.TraceAnnotation)
    eng = _engine(cfg, params, prefill_chunk=8)
    _serve(eng, _requests(2))                 # warm-up, profiler off
    jax.profiler.start_trace(str(tmp_path / "off"))
    jax.profiler.stop_trace()
    assert not {n for n in _host_event_names(tmp_path / "off")
                if n.startswith("serve.")}

    for r in _requests(2, rid0=10, prompt_len=12, gen=3):
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path / "on"))
    eng.step()                                # admit + chunk slab + decode
    eng.step()
    jax.profiler.stop_trace()
    names = _host_event_names(tmp_path / "on")
    want = {tracing.STEP, tracing.EVICT, tracing.ADMIT} | set(tracing.CHUNK) \
        | set(tracing.DECODE)
    assert want <= names, want - names
    while eng.has_work():
        eng.step()


# ---------------------------------------------------------------------------
# fff.decode_leaves on planted routing
# ---------------------------------------------------------------------------

def test_routing_stats_count_distinct_leaves_of_valid_rows():
    """``RoutingStats.distinct`` counts the leaves the valid rows read; for
    a forest, distinct leaf ids across trees."""
    cfg = api.fff_lib.FFFConfig(dim_in=8, dim_out=8, depth=3, leaf_width=4,
                                trees=2)
    idx = jnp.array([[[1, 1]], [[2, 5]], [[7, 0]]], jnp.int32)   # (3, 1, 2)
    out = api.FFFOutput(leaf_idx=idx)
    assert float(api.routing_stats_from(out, cfg).distinct) == 5.0
    valid = jnp.array([[True], [True], [False]])
    assert float(api.routing_stats_from(out, cfg, valid).distinct) == 3.0
    one = api.FFFOutput(leaf_idx=jnp.full((4, 1, 1), 6, jnp.int32))
    assert float(api.routing_stats_from(one, cfg).distinct) == 1.0


def _planted(params, cfg, x, spec):
    """Routes by a marker planted in the site's ``node_b2``: 0 sends every
    row to leaf 0, anything else sends row b to leaf b (mod leaves)."""
    spread = jnp.arange(x.shape[0]) % cfg.num_leaves
    idx = jnp.broadcast_to(spread.reshape((-1,) + (1,) * (x.ndim - 1)),
                           x.shape[:-1] + (cfg.trees,))
    idx = jnp.where(params["node_b2"].sum() != 0, idx, 0).astype(jnp.int32)
    return jnp.zeros_like(x), api.FFFOutput(
        leaf_idx=idx, overflow_fraction=jnp.zeros((), jnp.float32))


@pytest.fixture
def planted_backend():
    api.register_backend("infer", PLANTED, _planted)
    yield PLANTED
    api._REGISTRY.pop(("infer", PLANTED))


def _with_markers(params, markers):
    """The params with layer i's FFF ``node_b2`` set to ``markers[i]``."""
    stack = [dict(p) for p in params["stack"]]
    ffn = dict(stack[0]["ffn"])
    b2 = ffn["node_b2"]
    ffn["node_b2"] = jnp.broadcast_to(
        jnp.asarray(markers, b2.dtype).reshape((-1,) + (1,) * (b2.ndim - 1)),
        b2.shape)
    stack[0]["ffn"] = ffn
    return {**params, "stack": stack}


@pytest.mark.parametrize("markers,want", [((0, 0), 1.0), ((0, 1), 2.0)])
def test_decode_leaves_exact_on_planted_routing(tiny, planted_backend,
                                                markers, want):
    """3 live rows of 4 slots.  Both sites on one leaf: 1.  Site 0 on one
    leaf, site 1 spread over the live rows' 3 leaves: (1 + 3) / 2 — the
    free slot's phantom row would make it 2.5."""
    cfg, params = tiny
    eng = _engine(cfg, _with_markers(params, markers),
                  fff_backend=planted_backend, max_prefills_per_step=4)
    t0 = eng.now()
    _serve(eng, _requests(3, rid0=100, gen=4))
    got = tracing.samples(tracing.DECODE_LEAVES, t0, eng.now() + 1.0,
                          engine=eng._trace_id)
    assert got, "no decode step recorded the counter"
    # the three requests decode together until they finish at once
    assert got == [want] * len(got)
    assert eng.poll_metrics().decode_leaves_mean == pytest.approx(want)


def test_samples_of_the_newest_engine(tiny):
    """Without an engine id, ``samples`` reads the engine built last."""
    cfg, params = tiny
    first = _engine(cfg, params)
    _serve(first, _requests(1, rid0=200, gen=3))
    second = _engine(cfg, params)
    assert second._trace_id > first._trace_id
    steps = _serve(second, _requests(1, rid0=300, gen=2))
    newest = tracing.samples(tracing.RECOMPILES, 0.0, 1e9)
    assert len(newest) == steps           # one sample per step
    assert newest == tracing.samples(tracing.RECOMPILES, 0.0, 1e9,
                                     engine=second._trace_id)


# ---------------------------------------------------------------------------
# engine.recompiles
# ---------------------------------------------------------------------------

def test_recompiles_zero_in_steady_serving_then_one_on_a_new_shape(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, prefill_chunk=8)
    _serve(eng, _requests(2, rid0=400))                     # warm-up
    t_warm = eng.now()
    _serve(eng, _requests(4, rid0=410, prompt_len=11))      # steady
    assert set(tracing.samples(tracing.RECOMPILES, t_warm, eng.now(),
                               engine=eng._trace_id)) == {0.0}
    assert not any(eng.recompiles().values())

    # new weights of another dtype: the decode program traces again
    for r in _requests(1, rid0=420, prompt_len=5, gen=4):
        eng.submit(r)
    eng.step()                           # admit, one slab, first decode
    assert eng.slots[0] is not None and not eng.slots[0].prefilling
    eng.params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        eng.params)
    t_new = eng.now()
    eng.step()                                               # decode only
    assert tracing.samples(tracing.RECOMPILES, t_new, eng.now() + 1.0,
                           engine=eng._trace_id) == [1.0]
    assert eng.recompiles()["decode"] == 1
    while eng.has_work():
        eng.step()
    assert eng.poll_metrics().recompiles["decode"] == 1


# ---------------------------------------------------------------------------
# operator surfaces
# ---------------------------------------------------------------------------

def test_engine_metrics_carry_the_counters(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    _, m = eng.run(_requests(3, rid0=500))
    assert 1.0 <= m.decode_leaves_mean <= eng.num_leaves
    assert m.recompiles == {k: 0 for k in eng.compiled_shapes()}
    d = m.as_dict()
    assert d["decode_leaves_mean"] == m.decode_leaves_mean
    assert d["recompiles"] == m.recompiles
    polled = eng.poll_metrics().as_dict()
    assert {"decode_leaves_mean", "recompiles"} <= set(polled)


def test_metrics_json_carries_the_counters(tmp_path):
    from repro.launch import serve
    out = tmp_path / "metrics.json"
    serve.main(["--arch", "internlm2-20b", "--reduced", "--engine",
                "continuous", "--batch", "2", "--requests", "2",
                "--prompt-len", "8", "--gen", "3", "--metrics-json",
                str(out)])
    doc = json.loads(out.read_text())
    assert doc["decode_leaves_mean"] >= 1.0
    assert set(doc["recompiles"]) == set(doc["compiled_shapes"])
    assert not any(doc["recompiles"].values())
