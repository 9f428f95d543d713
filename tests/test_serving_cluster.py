"""Disaggregated prefill/decode cluster tests (DESIGN.md §12).

Tiers:
* host-only — VirtualClock, placement scoring, GlobalPrefixMap, and the
  ClusterMonitor's liveness/backoff/straggler/watermark policies run
  against synthetic views in pure virtual time (zero sleeps, zero jax);
* handoff tier — extract/install round-trips KV pages between two real
  engines and the receiver decodes token-for-token what a single engine
  would have produced;
* cluster tier — a LocalBus fleet (router + prefill + decode workers)
  serves mixed workloads with exact single-engine parity, survives a
  decode-worker kill mid-stream with zero lost or duplicated tokens
  (request replay from the prompt + Done dedup), honors drain semantics,
  autoscales on queue pressure, and keeps the per-worker compile contract
  at single-engine counts (decode workers never compile admit; prefill
  workers never compile decode).
"""
import numpy as np
import pytest

import jax

from repro.cluster import (ClusterConfig, ClusterWorker, GlobalPrefixMap,
                           LocalBus, Router, WorkerView, choose_decode,
                           choose_prefill)
from repro.cluster import handoff as handoff_lib
from repro.cluster.control import (ClusterMonitor, ControlConfig,
                                   DrainWorker, MarkDead, Respawn,
                                   SpawnDecode)
from repro.cluster.placement import overlap
from repro.configs import registry
from repro.distributed import StragglerConfig
from repro.models import lm
from repro.serving import (ContinuousBatchingEngine, EngineConfig, Request,
                           tracing)
from repro.serving.engine import VirtualClock

# ---------------------------------------------------------------------------
# host-only tier
# ---------------------------------------------------------------------------


def test_virtual_clock():
    vc = VirtualClock(start=2.0)
    assert vc() == 2.0
    assert vc.advance(0.5) == 2.5
    assert vc() == 2.5
    with pytest.raises(ValueError):
        vc.advance(-0.1)


def test_engine_accepts_injected_clock():
    """now() runs entirely on the injected clock — no wall time."""
    cfg = registry.get_config("internlm2-20b", ffn="fff").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    vc = VirtualClock(start=100.0)
    eng = ContinuousBatchingEngine(
        params, cfg, EngineConfig(num_slots=2, max_len=32,
                                  max_prompt_len=16, seed=0), clock=vc)
    assert eng.now() == 0.0
    vc.advance(3.0)
    assert eng.now() == 3.0


def test_overlap_and_decode_scoring():
    assert overlap(None, np.ones(4)) == 0.0
    assert overlap(np.ones(4), np.zeros(4)) == 0.0
    assert overlap(np.array([1.0, 0]), np.array([1.0, 0])) == \
        pytest.approx(1.0)
    base = dict(pages_total=64, queue_depth=0, active_slots=0, num_slots=4)
    views = {
        "d0": WorkerView(wid="d0", role="decode", pages_free=64,
                         occupancy=np.array([1.0, 0.0]), **base),
        "d1": WorkerView(wid="d1", role="decode", pages_free=64,
                         occupancy=np.array([0.0, 1.0]), **base),
    }
    # leaf-overlap steers AWAY from the worker already loaded on our leaves
    assert choose_decode(views, np.array([1.0, 0.0])) == "d1"
    assert choose_decode(views, np.array([0.0, 1.0])) == "d0"
    # page headroom dominates when footprints are flat
    views["d0"].pages_free = 4
    assert choose_decode(views, None) == "d1"
    # draining / full workers are never placed on
    views["d1"].draining = True
    views["d0"].draining = True
    assert choose_decode(views, None) is None


def test_choose_prefill_affinity_and_fallback():
    mk = lambda wid, q: WorkerView(wid=wid, role="prefill", num_slots=2,
                                   queue_depth=q)
    views = {"p0": mk("p0", 4), "p1": mk("p1", 0)}
    assert choose_prefill(views, None) == "p1"          # least loaded
    assert choose_prefill(views, "p0") == "p0"          # affinity wins
    views["p0"].draining = True
    assert choose_prefill(views, "p0") == "p1"          # unless draining


def test_global_prefix_map():
    m = GlobalPrefixMap(page_size=4)
    sys_prefix = list(range(100, 108))                  # two chunks
    m.insert(sys_prefix, "p0")
    assert m.lookup(sys_prefix + [1, 2, 3, 4]) == "p0"
    assert m.lookup([9, 9, 9, 9]) is None
    assert m.lookup([1, 2]) is None                     # sub-chunk: no key
    m.insert([9, 9, 9, 9], "p1")
    assert m.lookup([9, 9, 9, 9, 5]) == "p1"
    m.drop_worker("p0")
    assert m.lookup(sys_prefix) is None
    assert m.lookup([9, 9, 9, 9]) == "p1"


def _mk_views(**extra):
    views = {
        "p0": WorkerView(wid="p0", role="prefill", num_slots=2),
        "d0": WorkerView(wid="d0", role="decode", num_slots=4,
                         pages_free=64, pages_total=64),
        "d1": WorkerView(wid="d1", role="decode", num_slots=4,
                         pages_free=64, pages_total=64),
    }
    for wid, kw in extra.items():
        for k, v in kw.items():
            setattr(views[wid], k, v)
    return views


def test_monitor_heartbeat_timeout_and_backoff_respawn():
    vc = VirtualClock()
    mon = ClusterMonitor(ControlConfig(heartbeat_timeout=1.0,
                                       max_restarts=2, backoff_base=0.5,
                                       scale_up_watermark=1e9,
                                       scale_down_watermark=-1.0), vc)
    views = _mk_views()
    for wid in views:
        mon.observe_heartbeat(wid, vc())
    assert mon.tick(views, 0) == []                     # everyone fresh
    vc.advance(0.5)
    for wid in ("p0", "d1"):
        mon.observe_heartbeat(wid, vc())
    vc.advance(0.7)                                     # d0 now stale (1.2s)
    acts = mon.tick(views, 0)
    assert acts == [MarkDead("d0")]                     # death detected once
    assert mon.tick(views, 0) == []                     # not re-reported
    for wid in ("p0", "d1"):                            # survivors stay fresh
        mon.observe_heartbeat(wid, vc())
    vc.advance(0.5)                                     # backoff elapses
    acts = mon.tick(views, 0)
    assert acts == [Respawn("decode")]


def test_monitor_restart_budget_stops_respawns():
    vc = VirtualClock()
    mon = ClusterMonitor(ControlConfig(heartbeat_timeout=0.1,
                                       max_restarts=1, backoff_base=0.0,
                                       scale_up_watermark=1e9,
                                       scale_down_watermark=-1.0), vc)
    views = _mk_views()
    vc.advance(1.0)
    acts = mon.tick(views, 0)                           # all 3 time out
    # one respawn per role from the budget; the second decode death gets
    # nothing (budget 1), so the fleet stops flapping
    assert sum(isinstance(a, MarkDead) for a in acts) == 3
    assert sum(isinstance(a, Respawn) for a in acts) == 2


def test_monitor_elastic_watermarks():
    vc = VirtualClock()
    mon = ClusterMonitor(ControlConfig(heartbeat_timeout=1e9,
                                       scale_up_watermark=3.0,
                                       scale_down_watermark=0.5,
                                       watermark_ewma=1.0,
                                       scale_cooldown=1.0, min_decode=1,
                                       max_decode=4), vc)
    views = _mk_views()
    acts = mon.tick(views, 10)                          # heavy queue
    assert acts == [SpawnDecode()]
    assert mon.tick(views, 10) == []                    # cooldown holds
    vc.advance(1.5)
    assert mon.tick(views, 10) == [SpawnDecode()]
    vc.advance(1.5)
    acts = mon.tick(views, 0)                           # idle fleet drains
    assert acts == [DrainWorker("d1", reason="scale_down")]
    assert len(mon.scale_events) == 3


def test_monitor_straggler_drains_slow_decode_worker():
    vc = VirtualClock()
    mon = ClusterMonitor(
        ControlConfig(heartbeat_timeout=1e9, scale_up_watermark=1e9,
                      scale_down_watermark=-1.0,
                      straggler=StragglerConfig(window=16, slow_factor=1.5,
                                                eject_after=3,
                                                min_history=4)), vc)
    views = _mk_views()
    t = {"p0": 0.0, "d0": 0.0, "d1": 0.0}
    acts = []
    for _ in range(10):
        for wid, dt in (("p0", 0.1), ("d0", 0.1), ("d1", 0.5)):
            t[wid] += dt                                # d1 beats 5x slower
            mon.observe_heartbeat(wid, t[wid])
        acts = mon.tick(views, 0)
        if acts:
            break
    assert acts == [DrainWorker("d1", reason="straggler")]


# ---------------------------------------------------------------------------
# engine + cluster tiers (one module-scoped model)
# ---------------------------------------------------------------------------

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = registry.get_config("internlm2-20b", ffn="fff").reduced()
    params = lm.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ecfg(role, **kw):
    defaults = dict(num_slots=2 if role == "prefill" else 4, max_len=48,
                    max_prompt_len=16, page_size=PAGE, seed=0)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _requests(n, seed=7, max_new=6, lo=4, hi=17):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(1, 256,
                                               int(rng.integers(lo, hi))),
                    max_new_tokens=max_new) for i in range(n)]


def _cluster(cfg, params, *, n_prefill=1, n_decode=2, control=None,
             failure_hooks=None, engine_kw=None):
    vc = VirtualClock()
    engines = {}

    def factory(wid, role):
        eng = ContinuousBatchingEngine(params, cfg,
                                       _ecfg(role, **(engine_kw or {})),
                                       clock=vc)
        engines[wid] = eng
        hook = (failure_hooks or {}).get(wid)
        return ClusterWorker(wid, role, eng, failure_hook=hook)

    bus = LocalBus(factory, clock=vc)
    ctrl = control or ControlConfig(heartbeat_timeout=0.05, max_restarts=3,
                                    scale_up_watermark=1e9,
                                    scale_down_watermark=-1.0)
    router = Router(bus, ClusterConfig(n_prefill=n_prefill,
                                       n_decode=n_decode, page_size=PAGE,
                                       control=ctrl), clock=vc)
    router.start()
    return router, engines, vc


def test_handoff_roundtrip_matches_local_decode(model):
    """extract → install between two engines: the receiver finishes the
    request token-for-token as the engine that keeps the slot."""
    cfg, params = model
    reqs = _requests(2, seed=3)
    mirror = [Request(rid=r.rid, prompt=r.prompt,
                      max_new_tokens=r.max_new_tokens) for r in reqs]

    src = ContinuousBatchingEngine(params, cfg, _ecfg("prefill"))
    dst = ContinuousBatchingEngine(params, cfg, _ecfg("decode"))
    ref = ContinuousBatchingEngine(params, cfg, _ecfg("decode"))
    want, _ = ref.run(mirror)

    for r in reqs:
        src.submit(r)
    src._evict_finished()
    src._admit()                           # monolithic: prefill happens here
    handoffs = []
    for i, st in enumerate(src.slots):
        if st is not None and st.tokens and not st.done:
            h = handoff_lib.extract(src, i)
            assert h.n_pages == -(-len(st.request.prompt) // PAGE)
            assert h.nbytes > 0
            handoffs.append(h)
            src.release_slot(i, record_result=False)
    assert len(handoffs) == 2
    assert all(s is None for s in src.slots)            # fully released
    # only the prefix index still pins pages (published-prefix retention)
    src.prefix.reclaim(src.pool.num_pages)
    assert src.pool.pages_free == src.pool.num_pages

    for h in handoffs:
        assert handoff_lib.install(dst, h) is not None
    while dst.has_work():
        dst.step()
    got = sorted(dst.results, key=lambda r: r.rid)
    assert [list(g.tokens) for g in got] == [list(w.tokens) for w in want]
    assert dst.compiled_shapes()["install"] == 1        # one jit, reused


def test_cluster_parity_and_compile_contract(model):
    """LocalBus fleet output is byte-identical to one engine serving the
    same batch; each worker's compile ledger stays at single-engine
    counts for its role only."""
    cfg, params = model
    reqs = _requests(8, seed=11)
    router, engines, _ = _cluster(cfg, params)
    res = router.run(reqs, max_ticks=4000)

    ref = ContinuousBatchingEngine(params, cfg, _ecfg("decode"))
    want, _ = ref.run([Request(rid=r.rid, prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens)
                       for r in reqs])
    assert [(r.rid, list(r.tokens), r.finish_reason) for r in res] == \
        [(w.rid, list(w.tokens), w.finish_reason) for w in want]

    cm = router.cluster_metrics()
    assert cm["worker_restarts"] == 0
    assert cm["replayed_requests"] == 0
    assert cm["handoff_bytes"] > 0
    for wid, eng in engines.items():
        shapes = eng.compiled_shapes()
        if wid.startswith("p"):
            assert shapes["admit"] == 1 and shapes["decode"] == 0
        else:
            assert shapes["decode"] == 1 and shapes["admit"] == 0
            assert shapes.get("install", 0) == 1
    m = router.metrics()
    assert m.n_requests == 8 and m.ttft.mean_ms > 0


def test_cluster_kill_decode_worker_exact_replay(model):
    """SIGKILL-equivalent mid-stream: every request still completes with
    output exactly equal to lm.generate — zero lost or duplicated
    tokens — and exactly one respawn happens."""
    cfg, params = model
    reqs = _requests(8, seed=7, max_new=8)
    router, engines, _ = _cluster(
        cfg, params, failure_hooks={"d0": lambda n: n == 6},
        engine_kw=dict(prefill_chunk=8, prefill_budget=2))
    res = router.run(reqs, max_ticks=6000)
    cm = router.cluster_metrics()
    assert len(res) == len(reqs)                        # zero lost
    assert cm["worker_restarts"] == 1
    assert cm["replayed_requests"] >= 1
    assert cm["duplicate_results"] == 0                 # zero duplicated
    for r in res:
        prompt = np.asarray(r.prompt)[None, :]
        want = lm.generate(params, cfg, prompt, steps=len(r.tokens),
                           max_len=48)[0, prompt.shape[1]:]
        assert list(r.tokens) == list(np.asarray(want))
    # the killed worker is gone; its replacement carries a fresh wid
    assert "d0" not in router.views and "d2" in router.views
    # chunked prefill keeps the slab ledger at 1 per row bucket on the
    # prefill worker, and its slabs used only those buckets
    p0 = engines["p0"]
    family = {"prefill_chunk_1": 1, "prefill_chunk_2": 1}   # 2 slots
    assert {k: v for k, v in p0.compiled_shapes().items()
            if k.startswith("prefill_chunk_")} == family
    reached = tracing.samples(tracing.SLAB_ROWS, 0.0, float("inf"),
                              engine=p0._trace_id)
    assert reached and {f"prefill_chunk_{int(r)}" for r in reached} <= \
        set(family)


def test_cluster_drain_blocks_new_admissions(model):
    """Drain: in-flight work completes, queued work is never admitted."""
    cfg, params = model
    reqs = _requests(6, seed=5)
    router, engines, _ = _cluster(cfg, params)
    for r in reqs[:2]:
        router.submit(r)
    for _ in range(3):                                  # get them in flight
        router.step()
    assert sum(1 for s in router.states.values()
               if s.phase != "queued") == 2
    router.drain_all()
    for r in reqs[2:]:
        router.submit(r)
    for _ in range(200):
        router.step()
        if all(router.states[r.rid].phase == "done" for r in reqs[:2]):
            break
    assert all(router.states[r.rid].phase == "done" for r in reqs[:2])
    assert all(router.states[r.rid].phase == "queued" for r in reqs[2:])
    # drained workers have left the fleet after their goodbye handshake
    for _ in range(20):
        router.step()
    assert not router.views


def test_cluster_elastic_scale_up_then_down(model):
    """Queue pressure spawns a decode worker; the drained idle fleet
    scales back down."""
    cfg, params = model
    ctrl = ControlConfig(heartbeat_timeout=1e9, scale_up_watermark=3.0,
                         scale_down_watermark=0.5, watermark_ewma=1.0,
                         scale_cooldown=0.02, min_decode=1, max_decode=2)
    router, engines, _ = _cluster(cfg, params, n_decode=1, control=ctrl)
    res = router.run(_requests(10, seed=9), max_ticks=6000)
    assert len(res) == 10
    actions = [e["action"] for e in router.cluster_metrics()["scale_events"]]
    assert "scale_up" in actions
    assert len([w for w in engines if w.startswith("d")]) == 2
    # after the work drains, the idle fleet sheds the extra worker
    for _ in range(400):
        router.step()
        if "scale_down" in [e["action"] for e in
                            router.cluster_metrics()["scale_events"]]:
            break
    assert "scale_down" in [e["action"] for e in
                            router.cluster_metrics()["scale_events"]]


def test_cluster_prefix_affinity_routes_to_publisher(model):
    """Prompts sharing a system prefix land on the prefill worker that
    published it, where admission allocates shared pages."""
    cfg, params = model
    rng = np.random.default_rng(13)
    system = rng.integers(1, 256, PAGE)                 # one full page
    reqs = [Request(rid=i,
                    prompt=np.concatenate([system,
                                           rng.integers(1, 256, 4)]),
                    max_new_tokens=4) for i in range(6)]
    router, engines, _ = _cluster(cfg, params, n_prefill=2)
    res = router.run(reqs, max_ticks=4000)
    assert len(res) == 6
    assert len(router.prefix_map) > 0
    hits = sum(e.n_prefix_hit_tokens for w, e in engines.items()
               if w.startswith("p"))
    assert hits > 0                                     # pages were shared


class _LiveProc:
    def is_alive(self):
        return True


def test_procbus_refuses_more_workers_than_tpu_chips(monkeypatch):
    """On a TPU host a chip belongs to one process: a second JAX worker on
    a one-chip host must be refused up front, naming the cause, before any
    process starts."""
    from repro.cluster import bus as bus_lib
    b = bus_lib.ProcBus(lambda wid, role: None)
    b._procs["p0"] = (_LiveProc(), None)
    monkeypatch.setattr(bus_lib, "host_tpu_chips", lambda: 1)
    with pytest.raises(RuntimeError, match="chip"):
        b.spawn("d0", "decode")
    assert sorted(b._procs) == ["p0"]


def test_host_tpu_chips_is_zero_when_jax_is_held_to_cpu(monkeypatch):
    from repro.cluster import bus as bus_lib
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bus_lib.host_tpu_chips() == 0
