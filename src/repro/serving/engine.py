"""Continuous-batching serving engine (DESIGN.md §9, §11).

Owns a request queue, an admission scheduler, a PAGED KV-cache pool with a
host-side page allocator + prefix index (``serving/paging.py``), and
interleaved prefill/decode over FIXED compiled shapes:

* KV state lives in a fixed page pool (``EngineConfig.page_size`` tokens
  per page) indexed through per-slot page tables; admission installs a
  slot's table in one batched dispatch (``lm.cache_admit``) and eviction is
  pure host-side refcount bookkeeping — no device work at all.
* Admissions consult a radix prefix index over token ids: a prompt whose
  leading full pages are already cached maps them read-only (refcounted,
  shared across slots) and prefills ONLY the novel suffix — the
  shared-system-prompt workload prefill drops from O(prompt) to O(suffix).
  A prompt fully covered by shared pages copy-on-writes its last matched
  page so the final token re-prefills privately (first-token logits need
  it, and shared pages are never written).
* The decode batch is always ``(num_slots, 1)`` — free slots decode a dummy
  token whose output is ignored and never written — so the decode step
  compiles exactly once.
* Prompts prefill as chunk slabs right-padded to a small static set of
  *buckets* (powers of two up to ``max_prompt_len``), each bucket compiling
  once; only the admitted row of the ``(num_slots, bucket)`` slab is valid.
* With ``EngineConfig.prefill_chunk > 0`` prefill is CHUNKED instead: every
  in-flight prefill advances together through one ``(R, prefill_chunk)``
  slab per dispatch (``lm.prefill_chunk``), where R is the smallest power
  of two (capped at ``num_slots``) that holds the prefilling slots — one
  compiled shape per row bucket, all compiled at the first slab — at most
  ``prefill_budget`` dispatches per engine step, interleaved with decode —
  a long prompt's admission never stalls in-flight decode latency by more
  than the budgeted chunk work (DESIGN.md §9).  Under a ``drop`` /
  ``master_leaf`` overflow policy the slab stays ``(num_slots,
  prefill_chunk)``.  Decode steps mask cache writes for mid-prefill slots.
* Requests enter with prompt + sampling/stop params, decode together until
  EOS/max-tokens, then free their slot for waiting requests (their pages'
  refcounts drop; pages the prefix index still holds stay warm for future
  admissions until ``PrefixIndex.reclaim`` evicts them under pressure).
* With ``EngineConfig.spec_k > 0`` the decode step becomes a SPECULATIVE
  draft/verify round (DESIGN.md §10): ONE fused dispatch rolls out
  ``spec_k`` draft proposals per live slot (default draft: the target's own
  first period — ``serving/spec.py``) and verifies the ``(num_slots,
  spec_k + 1)`` slab with the target, then host-side rejection sampling
  emits 1..spec_k + 1 tokens with the target distribution preserved
  exactly.  Draft KV lives in a second pooled cache tree alongside the
  target's; both trees prefill/evict/truncate in the same dispatches as
  the target's.

Admission policy is pluggable (``serving/scheduler.py``); ``leaf_aware``
consumes the per-step FFF leaf-occupancy telemetry the engine collects via
``core/api.collect_routing`` to compose microbatches that minimize grouped-
dispatch capacity overflow, and ``weighted_leaf_aware`` adds weighted-fair
admission across ``Request.tenant`` classes (the queue keeps per-tenant FIFO
views — ``TenantQueues``).  Finished requests promote their measured leaf
occupancy into an online per-tenant ``RoutingProfileStore``
(``serving/profiles.py``), so hint-less tenants self-calibrate after their
first completions.

The engine is mesh-agnostic: pass ``trace_ctx`` (e.g. the launch layer's
``act.use_mesh`` wrapper) and every jitted call traces under it, so the same
loop serves single-device and expert-parallel (``grouped_ep``) topologies.
Sampling is host-side numpy (deterministic under ``EngineConfig.seed``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.models import lm
from repro.serving import metrics as metrics_lib
from repro.serving import paging
from repro.serving import spec as spec_lib
from repro.serving import tracing
from repro.serving.profiles import RoutingProfileStore
from repro.serving.request import Request, RequestResult, SlotState
from repro.serving.scheduler import Scheduler, SchedulerView, make_scheduler


class TenantQueues:
    """The engine's waiting queue: arrival order globally, FIFO per tenant.

    Schedulers receive the arrival-ordered view (``list(queue)``) — FCFS and
    ``leaf_aware`` never notice tenants exist — while QoS policies and the
    per-tenant metrics read the ``per_tenant`` map.  ``remove`` is identity-
    based (Request is eq=False), matching the admission path's contract."""

    def __init__(self):
        self._order: List[Request] = []
        self.per_tenant: Dict[str, deque] = {}

    def append(self, req: Request) -> None:
        self._order.append(req)
        self.per_tenant.setdefault(req.tenant, deque()).append(req)

    def remove(self, req: Request) -> None:
        self._order.remove(req)
        q = self.per_tenant[req.tenant]
        q.remove(req)
        if not q:
            del self.per_tenant[req.tenant]

    def depth(self, tenant: str) -> int:
        return len(self.per_tenant.get(tenant, ()))

    def __len__(self):
        return len(self._order)

    def __bool__(self):
        return bool(self._order)

    def __iter__(self):
        return iter(self._order)


class VirtualClock:
    """Deterministic engine/cluster clock for tests and the in-process
    ``cluster.LocalBus``: reading it never blocks and time only moves when
    the driver says so (``advance``), so heartbeat/timeout/elastic logic
    runs wall-time-free (ISSUE 8).  Inject via
    ``ContinuousBatchingEngine(..., clock=vc)`` — the engine detects the
    ``advance`` method and jumps straight to the next pending arrival
    instead of sleeping when idle."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def __call__(self) -> float:
        return self._t

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot advance a clock by {dt}")
        self._t += float(dt)
        return self._t


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


@dataclasses.dataclass
class EngineConfig:
    """Engine shape/policy knobs.  ``max_len`` bounds prompt + generation per
    slot (the pooled cache's sequence axis); ``prefill_buckets`` is the
    static set of compiled prompt shapes (default: powers of two from 16 up
    to ``max_prompt_len``).

    ``prefill_chunk``: 0 = monolithic prefill (one bucket-padded dispatch
    per admission, between decode steps); > 0 = chunked prefill — prompts
    advance ``prefill_chunk`` tokens at a time through a shared
    ``(R, prefill_chunk)`` slab of the prefilling slots (R a row bucket, at
    most ``num_slots``), at most ``prefill_budget`` slab dispatches per
    engine step.  Must be a power of two <= max_prompt_len (the slab's
    width comes from the same pow2 family as the buckets).  Smaller chunks
    / budget bound each step's admission work tighter (decode p99) at the
    cost of slower admission (TTFT); the scheduler-side ``max_prefilling``
    knob caps how many slots prefill concurrently (see
    serving/scheduler.py)."""
    num_slots: int = 8
    max_len: int = 128
    max_prompt_len: int = 64
    prefill_buckets: Tuple[int, ...] = ()
    max_prefills_per_step: int = 2
    prefill_chunk: int = 0
    prefill_budget: int = 1
    scheduler: str = "fcfs"
    scheduler_kw: dict = dataclasses.field(default_factory=dict)
    fff_backend: str = "auto"            # api.use_backend override, "auto" = none
    # fused decode megakernel (DESIGN.md §13): steer the DECODE dispatch
    # (and the draft rollout's seq-len-1 steps under spec_k) to the
    # ("infer", "pallas_decode") backend — routing + selected-leaf MLP +
    # combine in ONE pl.pallas_call instead of three.  Decode-only by
    # design: prefill/verify slabs keep fff_backend's resolution.  The
    # backend's supports predicate still applies (kernel-ineligible sites
    # and EP meshes fall through to the normal auto heuristics), so the
    # flag degrades gracefully rather than crashing a sharded engine.
    pallas_decode: bool = False
    # capacity factor: None = the configured backend's dispatch default.  A
    # value steers the LIVE dispatch (installed as the trace-time capacity
    # override via api.overrides — cf < 1.0 deliberately under-provisions
    # per-leaf capacity) and doubles as the scheduler's overflow proxy.
    capacity_factor: Optional[float] = None
    # overflow policy (DESIGN.md §14): what a capacity-bounded dispatch does
    # with over-capacity tokens — "exact_dense" (dense gather repair),
    # "master_leaf" (approximate: the always-on master term stands in alone;
    # requires FFF sites built with fff_master_leaf), "drop" (zeros).
    # None = the configured backend's default (api.default_overflow_policy).
    overflow_policy: Optional[str] = None
    telemetry: bool = True               # collect FFF routing stats
    occupancy_ewma: float = 0.5
    # online per-tenant routing profiles (serving/profiles.py): finished
    # requests' occupancy EWMA promotes into a per-tenant footprint that
    # seeds hint-less admissions — leaf_hint becomes optional/self-calibrating
    learn_profiles: bool = True
    profile_ewma: float = 0.3            # per-finished-request smoothing
    profile_min_updates: int = 1         # finished requests before serving
    # speculative decoding (DESIGN.md §10): spec_k > 0 replaces the decode
    # step with a draft/verify round — a draft model proposes spec_k tokens
    # per live slot in ONE fused rollout dispatch, the target verifies the
    # (num_slots, spec_k + 1) slab in one chunk dispatch, host-side
    # rejection sampling keeps the target distribution exact.
    # ``draft_config``: "self" / "self:N" = the target's own first N periods
    # (early-exit self-draft, shares params); a registry arch id = an
    # independent reduced draft (random init — correctness testing / a slot
    # for trained drafts); None = "self" (see serving/spec.build_draft).
    spec_k: int = 0
    draft_config: Optional[str] = None
    # paged KV cache (DESIGN.md §11): page_size 0 = one max_len-sized page
    # per slot (the contiguous layout, bit-for-bit) — prefix sharing is
    # structurally off there (no prompt ever fills a max_len page).
    # page_size > 0 carves the pool into fixed pages; num_pages 0 = auto
    # (num_slots * ceil(max_len / page_size), the contiguous footprint).
    # prefix_sharing gates the radix index — admission-time page REUSE —
    # independently of the paged layout itself.
    page_size: int = 0
    num_pages: int = 0
    prefix_sharing: bool = True
    # LRU cap on the per-tenant routing-profile store: an open multi-tenant
    # endpoint sees unbounded distinct tenant ids, and each profile row is
    # O(num_leaves) forever — cap generously and evict least-recently-
    # updated (warn-once on first eviction)
    profile_max_tenants: int = 1024
    seed: int = 0

    def buckets(self) -> Tuple[int, ...]:
        if self.prefill_buckets:
            return tuple(sorted(set(self.prefill_buckets)))
        return _pow2_buckets(min(16, self.max_prompt_len), self.max_prompt_len)



class ContinuousBatchingEngine:
    """Continuous-batching serving loop (module docstring has the design).

    Args:
        params:    the LM parameter tree (``lm.init``), possibly sharded.
        cfg:       the ``ModelConfig`` — decoder-only, attention mixers.
        ecfg:      engine shape/policy knobs (``EngineConfig``).
        scheduler: an admission ``Scheduler`` instance; default builds one
                   from ``ecfg.scheduler`` / ``ecfg.scheduler_kw``.
        trace_ctx: optional zero-arg context-manager factory entered around
                   every jitted call (e.g. ``launch/mesh.serving_context``'s
                   wrapper installing the SPMD mesh).

    Drive it either with ``run(requests)`` (serve a workload to completion,
    returns results + ``EngineMetrics``) or manually: ``submit`` then
    ``step()`` while ``has_work()``, polling ``poll_metrics()`` for live
    queue depth / latency / overflow telemetry."""

    def __init__(self, params, cfg, ecfg: EngineConfig,
                 scheduler: Optional[Scheduler] = None,
                 trace_ctx: Optional[Callable] = None,
                 draft: Optional[Tuple[dict, object]] = None,
                 mesh=None, clock: Optional[Callable[[], float]] = None):
        if cfg.encoder is not None or cfg.frontend != "none":
            raise ValueError("serving engine supports decoder-only token LMs")
        if any(b.mixer != "attn" for b in cfg.period):
            # recurrent mixers fold right-pad garbage into their state; the
            # engine's padded-prefill contract (DESIGN.md §9) needs
            # length-maskable caches
            raise ValueError("serving engine requires attention mixers "
                             "(padded prefill is length-masked, recurrent "
                             "state is not)")
        if ecfg.max_prompt_len >= ecfg.max_len:
            raise ValueError("max_prompt_len must leave room to generate "
                             "(max_prompt_len < max_len)")
        if ecfg.buckets()[-1] != ecfg.max_prompt_len:
            raise ValueError(
                f"prefill_buckets {ecfg.buckets()} must top out at "
                f"max_prompt_len {ecfg.max_prompt_len} — the two knobs "
                f"would otherwise disagree on the servable prompt length")
        if ecfg.prefill_chunk:
            c = ecfg.prefill_chunk
            if c < 1 or (c & (c - 1)):
                raise ValueError(
                    f"prefill_chunk {c} must be a power of two — the chunk "
                    f"slab's width comes from the same pow2 family as the "
                    f"prefill buckets (DESIGN.md §9)")
            if c > ecfg.max_prompt_len:
                raise ValueError(
                    f"prefill_chunk {c} exceeds max_prompt_len "
                    f"{ecfg.max_prompt_len}: every prompt would fit in one "
                    f"chunk — use monolithic prefill (prefill_chunk=0)")
            if ecfg.prefill_budget < 1:
                raise ValueError("prefill_budget must be >= 1 when chunked "
                                 "prefill is on")
        if ecfg.spec_k < 0:
            raise ValueError(f"spec_k {ecfg.spec_k} must be >= 0")
        if ecfg.page_size < 0 or ecfg.page_size > ecfg.max_len:
            raise ValueError(f"page_size {ecfg.page_size} must be in "
                             f"[0, max_len {ecfg.max_len}]")
        _page = ecfg.page_size or ecfg.max_len
        _ppr = -(-ecfg.max_len // _page)          # pages per slot, max
        if ecfg.num_pages and ecfg.num_pages < _ppr:
            raise ValueError(
                f"num_pages {ecfg.num_pages} cannot cover even one "
                f"max-length slot ({_ppr} pages of {_page} tokens)")
        if ecfg.draft_config is not None and not ecfg.spec_k:
            raise ValueError("draft_config is set but spec_k == 0 — "
                             "speculation is off, the draft would be dead "
                             "weight (set spec_k > 0 or drop draft_config)")
        if (ecfg.overflow_policy is not None
                and ecfg.overflow_policy not in api.OVERFLOW_POLICIES):
            raise ValueError(
                f"overflow_policy {ecfg.overflow_policy!r} not in "
                f"{api.OVERFLOW_POLICIES}")
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.num_leaves = next(
            (2 ** b.ffn.fff_depth for b in cfg.period if b.ffn.kind == "fff"),
            0)
        fff_spec = next((b.ffn for b in cfg.period if b.ffn.kind == "fff"),
                        None)
        # the first FFF site's layer config, for predicting what the auto
        # resolver will dispatch (the scheduler's capacity proxy)
        from repro.nn import mlp as mlp_lib
        self._site_cfg = None if fff_spec is None else mlp_lib.make_fff_config(
            fff_spec, cfg.d_model, param_dtype=cfg.param_dtype,
            accum_dtype=cfg.accum_dtype)
        if (ecfg.overflow_policy == "master_leaf"
                and self._site_cfg is not None
                and not self._site_cfg.master_leaf):
            # fail at construction, not at first trace: the repair term
            # does not exist in this model (DESIGN.md §14)
            raise ValueError(
                'overflow_policy="master_leaf" needs FFF sites built with '
                "fff_master_leaf=True — this model has no master term to "
                "stand in for dropped tokens")
        self.scheduler = scheduler or make_scheduler(ecfg.scheduler,
                                                     **ecfg.scheduler_kw)
        self._trace_ctx = trace_ctx
        self._topology: Optional[Tuple[int, float]] = None
        self._policy: Optional[str] = None    # set alongside _topology

        S, L = ecfg.num_slots, ecfg.max_len
        # the page pool (DESIGN.md §11): device side is a dumb pool + per-
        # slot tables (prealloc=False — all entries start at the unmapped
        # sentinel); the host-side allocator + prefix index own the mapping
        self._page = _page
        self._ppr = _ppr
        self._num_pages = ecfg.num_pages or S * _ppr
        self.pool = paging.PagePool(self._num_pages, self._page)
        self.prefix = paging.PrefixIndex(self.pool)
        self._slot_pages: List[list] = [[] for _ in range(S)]
        self._alloc_len = np.zeros((S,), np.int32)   # pages * page_size
        self._shared_len = np.zeros((S,), np.int32)  # prefix-hit boundary
        self.n_prefix_hit_tokens = 0
        self.n_cow_copies = 0
        self.n_prefill_tokens = 0
        self.caches = lm.init_caches(cfg, S, L, page_size=self._page,
                                     num_pages=self._num_pages,
                                     prealloc=False)
        # pin the pool's shardings ONCE, at allocation, under the serving
        # mesh (subsumes re-deriving cache placement per dispatch): jitted
        # cache-threading calls then see committed inputs and keep the
        # layout stable across donation round-trips
        self._mesh = mesh
        self._cache_sh: dict = {}       # "target"/"draft" -> shardings
        if mesh is not None:
            self.caches = self._pin_caches(self.caches, mesh, "target")
        # speculative decoding state (spec_k > 0): the draft model's pooled
        # caches live ALONGSIDE the target's, slot-indexed identically, so
        # admission/eviction treat the pair as one unit.  _tlen/_dlen are
        # the host-authoritative cache lengths: verify appends k+1 positions
        # optimistically, host rejection decides how many survive, and the
        # NEXT rollout dispatch rolls both trees back to these (lengths are
        # metadata — the truncate costs no extra dispatch).
        self.spec = ecfg.spec_k > 0
        self.draft_params = self.draft_cfg = None
        self.draft_caches = None
        if self.spec:
            if draft is not None:
                self.draft_params, self.draft_cfg = draft
            else:
                self.draft_params, self.draft_cfg = spec_lib.build_draft(
                    ecfg.draft_config, params, cfg, seed=ecfg.seed)
            if any(b.mixer != "attn" for b in self.draft_cfg.period):
                raise ValueError("draft model requires attention mixers "
                                 "(same pooled-cache contract as the target)")
            if self.draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {self.draft_cfg.vocab_size} != target "
                    f"vocab {cfg.vocab_size}: rejection sampling compares "
                    f"the two distributions token-for-token")
            # the draft's pool mirrors the target's page geometry (same
            # allocator, same tables): draft K/V for a token is as
            # deterministic as the target's, so shared prompt pages are
            # valid for both trees
            self.draft_caches = lm.init_caches(self.draft_cfg, S, L,
                                               page_size=self._page,
                                               num_pages=self._num_pages,
                                               prealloc=False)
            if mesh is not None:
                self.draft_caches = self._pin_caches(self.draft_caches, mesh,
                                                     "draft")
            self._tlen = np.zeros((S,), np.int32)   # target cache lengths
            self._dlen = np.zeros((S,), np.int32)   # draft cache lengths
        self._spec_rounds = 0
        self.n_draft_tokens = 0
        self.n_accepted_tokens = 0
        self.slots: List[Optional[SlotState]] = [None] * S
        self.queue = TenantQueues()
        self.results: List[RequestResult] = []
        self.occupancy = np.zeros((S, max(self.num_leaves, 1)), np.float64)
        # whether a slot's occupancy row holds MEASURED telemetry (vs a
        # seeded hint/profile prior): only measured rows may promote into
        # the profile store — else telemetry-less serving would EWMA the
        # store's own output (or the client's hint) back into itself and
        # report "learned" profiles built from zero observations
        self._measured = np.zeros((S,), bool)
        # online per-tenant routing profiles, fed by _evict_finished
        self.profiles: Optional[RoutingProfileStore] = (
            RoutingProfileStore(self.num_leaves, ewma=ecfg.profile_ewma,
                                min_updates=ecfg.profile_min_updates,
                                max_tenants=ecfg.profile_max_tenants)
            if ecfg.learn_profiles and self.num_leaves else None)
        self._hint_mismatches = 0            # size-mismatched leaf_hints seen
        self._hint_warned = False            # warn once per engine
        # what a FREE slot decodes: its last occupant's final token (distinct
        # per-slot ids before first use — a constant would concentrate
        # startup phantom load on one leaf).  Free rows' outputs are
        # ignored, but they still
        # route through FFF sites and — under the drop-semantics "grouped"
        # backend — share per-leaf capacity with real tokens; feeding
        # in-distribution, naturally-spread tokens keeps that phantom load
        # from piling onto one leaf (exact backends: reference / pallas /
        # grouped_ep's repair are unaffected by construction)
        self._free_tok = (np.arange(S) % cfg.vocab_size).astype(np.int32)
        self._live_rids: set = set()            # queued or in a slot
        self._arrivals: Dict[int, float] = {}   # id(req) -> engine-clock s

        # every cache-threading entry point goes through _jit: it donates
        # the pooled caches and keeps their pinned layout (see there).  Each
        # is a function of this engine's own (jit's caches are keyed by the
        # function, so a shared one would count other engines' shapes in
        # compiled_shapes), named for the profiler's host events
        def decode_step(p, t, c, off, wm, lv):
            return lm.decode_step(p, cfg, t, c, off, write_mask=wm,
                                  token_valid=lv, with_stats=True)

        self._decode_jit = self._jit(decode_step, (2,),
                                     (None, "target", None))
        if self.spec:
            dcfg = self.draft_cfg
            # every spec-mode entry point that touches caches touches BOTH
            # trees in the SAME dispatch — prefill, chunk, admit, round —
            # so speculation adds zero dispatch overhead over plain serving
            # anywhere except the round itself (where it replaces k+1
            # decode dispatches with one).  Monolithic prefill is a chunk
            # slab at bucket width: only the admitted row is valid, and its
            # offset starts at the shared-prefix boundary (DESIGN.md §11).
            def chunk_both():          # a fresh function per compiled shape
                def prefill_chunk_both(p, dp, t, v, c, dc, off, rows=None):
                    return spec_lib.chunk_both(p, cfg, dp, dcfg, t, v, c, dc,
                                               off, rows)
                return self._jit(prefill_chunk_both, (4, 5),
                                 (None, "target", "draft", None, None))
            self._prefill_jits = {b: chunk_both() for b in ecfg.buckets()}
            self._chunk_factory = chunk_both
            def cache_admit_both(c, dc, ad, tb, ln, cs, cd):
                return (lm.cache_admit(c, ad, tb, ln, cs, cd),
                        lm.cache_admit(dc, ad, tb, ln, cs, cd))

            self._admit_jit = self._jit(cache_admit_both, (0, 1),
                                        ("target", "draft"))
            # the whole round — both trees' length rollback, k+1 scanned
            # draft decode steps with on-device sampling, and the target's
            # (num_slots, k+1) verify — in one compiled shape.  The
            # per-round PRNG key derives inside the trace from a traced
            # round counter, so the jit compiles once.
            def spec_round(p, dp, t0, c, dc, tl, dl, p0, wm, vl, lv, tp,
                           rnd):
                return spec_lib.spec_round(
                    p, cfg, dp, dcfg, t0, c, dc, tl, dl, p0, wm, vl, lv, tp,
                    jax.random.fold_in(jax.random.PRNGKey(ecfg.seed), rnd),
                    verify_cf=self._verify_cf(),
                    # the rollout's k+1 scanned draft steps are seq-len-1 —
                    # the megakernel's shape; the verify slab is not and
                    # keeps the normal resolution (DESIGN.md §13)
                    draft_backend=("pallas_decode" if ecfg.pallas_decode
                                   else None))

            self._spec_jit = self._jit(
                spec_round, (3, 4),
                (None, None, None, "target", "draft", None, None))
        else:
            def chunk():               # a fresh function per compiled shape
                def prefill_chunk(p, t, v, c, off, rows=None):
                    return lm.prefill_chunk(p, cfg, t, v, c, off, rows)
                return self._jit(prefill_chunk, (3,), (None, "target", None))
            self._prefill_jits = {b: chunk() for b in ecfg.buckets()}
            self._chunk_factory = chunk

            def cache_admit(c, ad, tb, ln, cs, cd):
                return lm.cache_admit(c, ad, tb, ln, cs, cd)

            self._admit_jit = self._jit(cache_admit, (0,), "target")
        # chunked prefill's slab jits, one per row bucket R (the pow2
        # family up to num_slots), all made at the first slab
        self._chunk_rows = _pow2_buckets(1, S)
        self._chunk_jits: Dict[int, Callable] = {}
        # per-slot raw leaf counts accumulated across a request's prefill
        # chunks; normalized into self.occupancy when its prefill completes
        self._prefill_counts = np.zeros((S, max(self.num_leaves, 1)),
                                        np.float64)

        # the engine clock is injectable (ISSUE 8): every timestamp —
        # arrivals, TTFT, decode latency, RequestResult times — reads
        # through _clock, so a VirtualClock makes the whole serving loop
        # (and cluster heartbeat/timeout logic above it) wall-time-free
        self._clock: Callable[[], float] = clock or time.monotonic
        self._t0 = self._clock()
        self.n_steps = 0
        self.n_prefills = 0
        self.n_chunks = 0
        self.decode_lat: List[float] = []
        # gaps between consecutive decode dispatches while work was in
        # flight: the stall-free-admission signal (a monolithic long-prompt
        # prefill lands in one of these gaps; chunked prefill bounds them)
        self.decode_interval_s: List[float] = []
        self._last_decode_end: Optional[float] = None
        # the counters of serving/tracing.py: this engine's id in their log,
        # the layer scan's period count (RoutingStats.distinct sums over
        # it), and the running sum and count of fff.decode_leaves samples
        self._trace_id = tracing.new_engine_id()
        self._n_periods = cfg.n_layers // len(cfg.period)
        self._leaves = [0.0, 0]
        # slot-weighted overflow accumulators, split by phase: admission
        # composes the *decode* batch, so decode overflow is the scheduler's
        # signal (spec verify dispatches land there too — they ARE the
        # target's decode); "draft" keeps the draft model's own routing out
        # of the target's numbers; prefill overflow is per-request.  Filler
        # rows cost nothing anywhere: the per-row validity mask routes them
        # to the FFF sentinel leaf, outside capacity and telemetry.
        self._overflow = {"prefill": [0.0, 0.0], "decode": [0.0, 0.0],
                          "draft": [0.0, 0.0]}

    # -- cache placement -----------------------------------------------------

    def _pin_caches(self, caches, mesh, which: str):
        """Commit the page pool (``which``: "target" or "draft") to its
        serving-mesh placement once, at allocation (ROADMAP: pin cache
        shardings under the EP mesh).  Every later jitted call donates the
        pinned buffers and returns them in the same layout (``_jit``), so
        the layout derived here is the layout for the engine's lifetime —
        and each entry point compiles once, not once per layout XLA happens
        to pick."""
        from repro.distributed import sharding as shard_lib
        specs = shard_lib.cache_specs(caches, mesh, self.ecfg.num_slots)
        self._cache_sh[which] = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
        return jax.tree_util.tree_map(jax.device_put, caches,
                                      self._cache_sh[which])

    def _jit(self, fn, donate: tuple, caches_out):
        """jit a cache-threading entry point.  The cache arguments
        ``donate`` are donated, so XLA updates the pool in place instead of
        copying it per token (the caller always rebinds the caches to the
        result; CPU has no donation and would warn per compile).  Under a
        serving mesh the pools ``fn`` returns keep their pinned layout:
        ``caches_out`` names the pool at each output position ("target",
        "draft", or None for outputs the compiler places), or is the pool's
        name when ``fn`` returns that pool alone."""
        kw = {} if jax.default_backend() == "cpu" \
            else {"donate_argnums": donate}
        if self._cache_sh:
            def pin(which):
                return None if which is None else self._cache_sh[which]
            kw["out_shardings"] = (pin(caches_out)
                                   if isinstance(caches_out, str)
                                   else tuple(map(pin, caches_out)))
        return jax.jit(fn, **kw)

    # -- clock ---------------------------------------------------------------

    def now(self) -> float:
        """Engine-clock seconds since construction (all Request arrival
        offsets and RequestResult timestamps are on this clock).  The
        clock source is injectable (``clock=`` at construction; default
        ``time.monotonic``) — a ``VirtualClock`` runs the loop in
        deterministic virtual time."""
        return self._clock() - self._t0

    # -- submission ----------------------------------------------------------

    def validate(self, req: Request) -> None:
        """Shape/uniqueness checks a request must pass to be servable;
        raises ValueError otherwise (``run`` fail-fasts its whole batch
        through this before serving anything)."""
        buckets = self.ecfg.buckets()
        if len(req.prompt) > buckets[-1]:
            raise ValueError(
                f"request {req.rid}: prompt len {len(req.prompt)} exceeds "
                f"max prefill bucket {buckets[-1]}")
        if len(req.prompt) + req.max_new_tokens > self.ecfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt + max_new_tokens exceeds "
                f"max_len {self.ecfg.max_len}")
        if req.rid in self._live_rids:
            # rid keys the scheduler's hold map and the sampling RNG stream;
            # two live requests sharing one would alias
            raise ValueError(f"request rid {req.rid} is already queued or "
                             f"active")

    def submit(self, req: Request,
               arrival_time: Optional[float] = None) -> None:
        """Enqueue a request.  Its arrival is recorded on the engine clock —
        submission time by default — in a side table (the caller's
        ``Request.arrival_time`` offset is never mutated, so request lists
        can be replayed on a warm engine)."""
        self.validate(req)
        if req.leaf_hint is not None and self.num_leaves and \
                (req.leaf_hint.size != self.num_leaves
                 or req.leaf_hint.sum() <= 0):
            # advisory, so never reject — but a silently dropped hint looks
            # exactly like a missing one, which hides client-side profile
            # bugs: warn once and count every occurrence (the
            # ``hint_mismatches`` metric).  Unusable = wrong width for this
            # model's leaf count, or zero mass (nothing to normalize) —
            # the same predicate the seeding/footprint paths discard by.
            self._hint_mismatches += 1
            if not self._hint_warned:
                self._hint_warned = True
                why = (f"size {req.leaf_hint.size} != num_leaves "
                       f"{self.num_leaves}"
                       if req.leaf_hint.size != self.num_leaves
                       else "zero mass")
                warnings.warn(
                    f"request {req.rid} (tenant {req.tenant!r}): unusable "
                    f"leaf_hint ({why}); ignoring it (counted in the "
                    f"hint_mismatches metric; further unusable hints warn "
                    f"only via the counter)", stacklevel=2)
        self._live_rids.add(req.rid)
        self._arrivals[id(req)] = (self.now() if arrival_time is None
                                   else arrival_time)
        self.queue.append(req)

    # -- trace contexts ------------------------------------------------------

    def _ctx(self):
        es = contextlib.ExitStack()
        if self._trace_ctx is not None:
            es.enter_context(self._trace_ctx())
        kw = {}
        if self.ecfg.fff_backend != "auto":
            kw.update(backend=self.ecfg.fff_backend, mode="infer")
        if self.ecfg.capacity_factor is not None:
            # the engine's capacity factor steers the LIVE dispatch, not
            # just the scheduler proxy — cf < 1.0 under-provisions on
            # purpose and the overflow policy decides what happens then
            kw["capacity_factor"] = self.ecfg.capacity_factor
        if self.ecfg.overflow_policy is not None:
            kw["overflow_policy"] = self.ecfg.overflow_policy
        if kw:
            es.enter_context(api.overrides(**kw))
        if self.ecfg.telemetry:
            es.enter_context(api.collect_routing())
        return es

    def _decode_backend_ctx(self):
        """The decode-only backend steer: under ``ecfg.pallas_decode`` the
        decode dispatch traces with the fused megakernel backend
        (DESIGN.md §13) while every other dispatch keeps ``fff_backend``'s
        resolution.  Trace-time thread-local, so it costs nothing once the
        decode jit is compiled."""
        if not self.ecfg.pallas_decode:
            return contextlib.nullcontext()
        return api.overrides(backend="pallas_decode", mode="infer")

    def _dispatch_topology(self) -> Tuple[int, Optional[float]]:
        """(token-axis shard count, capacity factor) the live FFF dispatch
        actually runs with — the scheduler's overflow proxy must match it,
        not the engine's nominal config.  ``auto`` resolves through
        ``api.resolve_backend`` (the real resolver, including supports
        predicates), evaluated under the trace contexts because the mesh
        accessors and overrides are trace-time thread-locals; cached — the
        mesh is fixed for the engine's lifetime.  Capacity factor None =
        exact per-token backend, no capacity bound to predict against."""
        if self._topology is None:
            from repro.distributed import act as dist_act
            backend = self.ecfg.fff_backend
            with self._ctx():
                g = dist_act.data_shard_count()
                m = dist_act.model_shard_count()
                if backend == "auto":
                    backend = (api.resolve_backend({}, self._site_cfg)
                               if self._site_cfg is not None else "reference")
            if backend in ("reference", "pallas", "pallas_decode"):
                self._topology = (1, None)     # exact: no capacity bound
                self._policy = None
            else:
                shards = g * m if backend == "grouped_ep" else g
                cf = (self.ecfg.capacity_factor
                      if self.ecfg.capacity_factor is not None
                      else api.default_capacity_factor(backend))
                self._topology = (shards, cf)
                self._policy = (self.ecfg.overflow_policy
                                if self.ecfg.overflow_policy is not None
                                else api.default_overflow_policy(backend))
        return self._topology

    def _overflow_policy(self) -> Optional[str]:
        """The overflow policy the live dispatch runs with (DESIGN.md §14);
        None when no capacity bound exists (exact backends never drop)."""
        self._dispatch_topology()
        return self._policy

    def _repair_counters(self, ovf0: Optional[dict] = None
                         ) -> Tuple[int, float]:
        """Host-side overflow-policy accounting from the routing-stats
        overflow accumulators: (estimated repaired (token, tree) slots,
        fraction of slots served by the master leaf alone).  ``ovf0`` rebases
        onto a per-run snapshot of ``self._overflow``; repairs are 0 under
        policy "drop" (nothing stands in) and the master fraction is nonzero
        only under "master_leaf"."""
        policy = self._overflow_policy()
        if policy in (None, "drop"):
            return 0, 0.0
        w = n = 0.0
        for k, acc in self._overflow.items():
            base = ovf0[k] if ovf0 else (0.0, 0.0)
            w += acc[0] - base[0]
            n += acc[1] - base[1]
        frac = (w / n if n else 0.0) if policy == "master_leaf" else 0.0
        return int(round(w)), frac

    def _verify_cf(self) -> Optional[float]:
        """Capacity factor for the speculative verify dispatch: the decode
        capacity factor scaled by the slab width ``k + 1``.  A verify slab
        is k+1 decode steps fused onto one token axis, so per-leaf capacity
        must scale with that axis — otherwise each verify token would see
        LESS capacity than the same token in plain decode (the per-leaf
        capacity floor is generous to small batches) and speculation would
        change serving numerics instead of just batching them.  None for
        exact backends (no capacity bound)."""
        _, cf = self._dispatch_topology()
        return None if cf is None else float(cf) * (self.ecfg.spec_k + 1)

    # -- telemetry -----------------------------------------------------------

    def _stats_rows(self, stats, phase: str,
                    weight_scale: float = 1.0) -> Optional[np.ndarray]:
        """Merge a per-site routing-stats tuple into per-batch-row leaf
        counts (B, E) for sites matching the engine's telemetry width, and
        fold the slot-weighted overflow into the running per-phase mean.

        ``RoutingStats.slots`` counts VALID tokens only — the per-row
        validity mask routes filler rows to the sentinel leaf, which
        ``bincount`` drops — so slab dispatches self-weight by real-token
        count and ``weight_scale`` stays 1.0 for them (it remains as an
        explicit discount hook for callers with out-of-band knowledge)."""
        if stats is None or self.num_leaves == 0:
            return None
        counts = None
        acc = self._overflow[phase]
        for s in stats:
            if s is None:
                continue
            c = np.asarray(s.leaf_counts, np.float64)
            w = float(s.slots) * weight_scale
            acc[0] += float(s.overflow) * w
            acc[1] += w
            if c.shape[-1] == self.num_leaves:
                counts = c if counts is None else counts + c
        return counts

    def _count_decode_leaves(self, stats) -> None:
        """Record ``fff.decode_leaves`` for one decode call: the distinct
        leaves each FFF site of the telemetry width read, the mean over
        those sites (``RoutingStats.distinct`` is summed over the layer
        scan's periods)."""
        if stats is None or self.num_leaves == 0:
            return
        got = [float(s.distinct) for s in stats if s is not None
               and s.leaf_counts.shape[-1] == self.num_leaves]
        if not got:
            return
        v = sum(got) / (len(got) * self._n_periods)
        self._leaves[0] += v
        self._leaves[1] += 1
        tracing.record(self.now(), self._trace_id, tracing.DECODE_LEAVES, v)

    def _update_occupancy(self, slot_rows: Sequence[int],
                          counts: Optional[np.ndarray],
                          measured: bool = True) -> None:
        """Fold per-row leaf counts into the occupancy EWMA.  ``measured``
        False (the draft model's histograms — a PRIOR on where the target's
        verify tokens will route, DESIGN.md §10) refines the footprint the
        schedulers read without promoting the row into profile-store
        eligibility: profiles must hold target-measured telemetry only."""
        if counts is None:
            return
        a = self.ecfg.occupancy_ewma
        for r in slot_rows:
            tot = counts[r].sum()
            if tot <= 0:
                continue
            if measured:
                self._measured[r] = True
            frac = counts[r] / tot
            prev = self.occupancy[r]
            self.occupancy[r] = frac if not prev.any() else \
                (1.0 - a) * prev + a * frac

    def overflow_mean(self, phase: Optional[str] = None) -> float:
        """Slot-weighted mean overflow_fraction; ``phase`` in
        {"prefill", "decode", "draft", None = all}."""
        keys = [phase] if phase else list(self._overflow)
        w = sum(self._overflow[k][0] for k in keys)
        n = sum(self._overflow[k][1] for k in keys)
        return w / n if n else 0.0

    # -- sampling (host-side, deterministic under seed) ----------------------

    def _sample(self, st: SlotState, logits_row: np.ndarray) -> int:
        if st.request.temperature <= 0.0:
            return int(logits_row.argmax())
        rng = np.random.default_rng(
            (self.ecfg.seed, st.request.rid, len(st.tokens)))
        z = logits_row / st.request.temperature
        return int((z + rng.gumbel(size=z.shape)).argmax())

    def _record_token(self, st: SlotState, tok: int) -> None:
        st.tokens.append(tok)
        st.total_len += 1
        req = st.request
        if req.eos_id is not None and tok == req.eos_id:
            st.done, st.finish_reason = True, "eos"
        elif len(st.tokens) >= req.max_new_tokens:
            st.done, st.finish_reason = True, "length"
        if st.done:
            st.finish_time = self.now()

    # -- the loop ------------------------------------------------------------

    def _evict_finished(self) -> None:
        for i, st in enumerate(self.slots):
            if st is None or not st.done:
                continue
            self.release_slot(i)

    def release_slot(self, i: int, record_result: bool = True) -> None:
        """Free slot ``i``: pages decref'd, occupancy promoted/reset, rid
        retired.  ``record_result=False`` is the cluster handoff path
        (``cluster/handoff.py``): a prefill worker that just shipped the
        slot's KV pages releases the slot WITHOUT minting a
        ``RequestResult`` — the receiving decode worker owns the request's
        lifecycle from here.  No device dispatch either way — the slot's
        stale table and length rows are harmless because every decode/chunk
        write is masked to live rows, and re-admission overwrites both."""
        st = self.slots[i]
        if st is None:
            return
        # free the slot's pages on the host: refcounts drop, and pages
        # nobody else holds (no other slot, not the prefix index) return
        # to the free list.
        self.pool.decref(self._slot_pages[i])
        self._slot_pages[i] = []
        self._alloc_len[i] = 0
        self._shared_len[i] = 0
        # promote the finished request's measured footprint into its
        # tenant's online routing profile BEFORE the row resets — this
        # is how leaf hints self-calibrate (ROADMAP: learn leaf hints
        # online).  _measured gates out rows that only ever held a
        # seeded prior (telemetry off / no FFF stats landed).
        if self.profiles is not None and self._measured[i] and \
                self.occupancy[i].any():
            self.profiles.update(st.request.tenant, self.occupancy[i])
        self.occupancy[i] = 0.0
        self._measured[i] = False
        self._prefill_counts[i] = 0.0
        if self.spec:
            self._tlen[i] = 0
            self._dlen[i] = 0
        # what this freed slot will decode while idle: the occupant's
        # last NON-EOS token — replaying the EOS id itself would pile
        # every freed slot's phantom routing onto the EOS token's leaf
        spread = [t for t in st.tokens if t != st.request.eos_id]
        self._free_tok[i] = (spread[-1] if spread
                             else int(st.request.prompt[-1]))
        self._live_rids.discard(st.request.rid)
        arrival = self._arrivals.pop(id(st.request), st.admitted_time)
        if record_result:
            self.results.append(RequestResult(
                rid=st.request.rid, prompt=st.request.prompt,
                tokens=np.asarray(st.tokens, np.int32),
                finish_reason=st.finish_reason,
                arrival_time=arrival,
                admitted_time=st.admitted_time,
                first_token_time=st.first_token_time,
                finish_time=st.finish_time,
                tenant=st.request.tenant,
                n_drafted=st.n_drafted,
                n_accepted=st.n_accepted))
        self.slots[i] = None

    def _bucket_for(self, n: int) -> int:
        return next(b for b in self.ecfg.buckets() if b >= n)

    def _seed_hint(self, slot: int, req: Request) -> None:
        """Seed the slot's occupancy row before any telemetry lands: the
        request's own ``leaf_hint`` if usable, else the tenant's learned
        routing profile (mismatched hints were counted at submit)."""
        h = req.leaf_hint
        if h is None or not self.num_leaves or h.size != self.num_leaves \
                or h.sum() <= 0:
            # same usability predicate as the schedulers' _footprint — a
            # zero-mass hint must fall through identically on both sides,
            # or admission and slot seeding would disagree on the footprint
            h = (self.profiles.lookup(req.tenant)
                 if self.profiles is not None else None)
        if h is not None and self.num_leaves and h.size == self.num_leaves \
                and h.sum() > 0:
            self.occupancy[slot] = h / h.sum()

    # -- paged admission (DESIGN.md §11) -------------------------------------

    def _plan_pages(self, req: Request) -> Optional[dict]:
        """Page plan for admitting ``req``: the longest indexed full-page
        prefix maps read-only shared pages; fresh pages cover the rest of
        ``len(prompt) + max_new_tokens``.  A fully-covered prompt
        copy-on-writes its last matched page (the final token must
        re-prefill privately: first-token logits, and shared pages are
        never written).  Returns None — request stays queued — when the
        pool can't cover the fresh pages even after reclaiming LRU index
        entries (OOM-of-pages is scheduler back-pressure, not an error)."""
        page = self._page
        L = len(req.prompt)
        n_total = -(-(L + req.max_new_tokens) // page)
        matched = (self.prefix.match(req.prompt) if self.ecfg.prefix_sharing
                   else [])
        shared = min(len(matched) * page, L - 1)   # >= 1 novel token always
        n_shared = shared // page
        shared_pages = list(matched[:n_shared])
        cow_src = matched[n_shared] if shared % page else None
        n_fresh = n_total - n_shared
        # hold the mapped + COW-source pages through reclaim/alloc — the
        # reclaim below must not free what this very admission depends on
        self.pool.incref(shared_pages)
        if cow_src is not None:
            self.pool.incref([cow_src])
        if self.pool.pages_free < n_fresh:
            self.prefix.reclaim(n_fresh)
        fresh = self.pool.alloc(n_fresh)
        if fresh is None:
            self.pool.decref(shared_pages)
            if cow_src is not None:
                self.pool.decref([cow_src])
            return None
        return {"pages": shared_pages + fresh, "shared_len": shared,
                "cow_src": cow_src,
                "cow_dst": fresh[0] if cow_src is not None else None}

    def _apply_admit(self, slot: int, plan: dict) -> None:
        """Install the plan's page table + shared-prefix length at ``slot``
        in one dispatch (``lm.cache_admit``; spec mode: both trees)."""
        S, sentinel = self.ecfg.num_slots, self._num_pages
        admit = np.zeros((S,), bool)
        admit[slot] = True
        tables = np.full((S, self._ppr), sentinel, np.int32)
        tables[slot, :len(plan["pages"])] = plan["pages"]
        lengths = np.zeros((S,), np.int32)
        lengths[slot] = plan["shared_len"]
        cow_src = np.full((S,), sentinel, np.int32)
        cow_dst = np.full((S,), sentinel, np.int32)
        if plan["cow_src"] is not None:
            cow_src[slot] = plan["cow_src"]
            cow_dst[slot] = plan["cow_dst"]
            self.n_cow_copies += 1
        args = (jnp.asarray(admit), jnp.asarray(tables),
                jnp.asarray(lengths), jnp.asarray(cow_src),
                jnp.asarray(cow_dst))
        with self._ctx():
            if self.spec:
                self.caches, self.draft_caches = self._admit_jit(
                    self.caches, self.draft_caches, *args)
            else:
                self.caches = self._admit_jit(self.caches, *args)
        if plan["cow_src"] is not None:
            # the COW copy is dispatched (device order protects it from any
            # later reuse of the source page) — drop the temporary hold
            self.pool.decref([plan["cow_src"]])
        self._slot_pages[slot] = list(plan["pages"])
        self._alloc_len[slot] = len(plan["pages"]) * self._page
        self._shared_len[slot] = plan["shared_len"]
        self.n_prefix_hit_tokens += plan["shared_len"]

    def _publish_prefix(self, slot: int) -> None:
        """Index the slot's full prompt pages for cross-request sharing —
        only now, at prefill COMPLETION: publishing at admission would let
        a racing request attend to pages whose K/V aren't written yet
        (racing admissions simply miss and prefill themselves)."""
        if not self.ecfg.prefix_sharing:
            return
        prompt = self.slots[slot].request.prompt
        n_full = len(prompt) // self._page
        if n_full:
            self.prefix.insert(prompt, self._slot_pages[slot][:n_full])

    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        n = min(len(free), self.ecfg.max_prefills_per_step)
        shards, cf = self._dispatch_topology()
        view = SchedulerView(
            occupancy=self.occupancy,
            active=np.asarray([s is not None for s in self.slots]),
            num_leaves=self.num_leaves,
            capacity_factor=cf,
            num_slots=self.ecfg.num_slots,
            dispatch_shards=shards,
            prefilling=np.asarray([s is not None and s.prefilling
                                   for s in self.slots]),
            profiles=self.profiles,
            # spec verify dispatches spec_k + 1 tokens per slot: the
            # scheduler's per-leaf capacity proxy must be normalized by the
            # same factor or it would predict overflow against a bound k+1
            # times too tight (see SchedulerView.leaf_capacity)
            tokens_per_slot=(self.ecfg.spec_k + 1) if self.spec else 1,
            pages_free=self.pool.pages_free)
        if self.ecfg.prefill_chunk:
            # the max_prefilling knob is chunked-only by contract (a
            # monolithic admission never *dwells* in the prefilling state,
            # so capping it would just throttle admission throughput)
            n = min(n, self.scheduler.admission_cap(view))
        if n <= 0:
            return
        chosen = self.scheduler.select(list(self.queue), n, view)
        for req in chosen:
            plan = self._plan_pages(req)
            if plan is None:
                # OOM of pages: the request (and the rest of this step's
                # picks) stays queued — evictions or index reclaim free
                # pages on a later step, and the scheduler sees the
                # pressure via SchedulerView.pages_free
                break
            self.queue.remove(req)
            slot = free.pop(0)
            if self.ecfg.prefill_chunk:
                self._admit_chunked(req, slot, plan)
            else:
                self._admit_monolithic(req, slot, plan)

    def _admit_monolithic(self, req: Request, slot: int, plan: dict) -> None:
        """One bucket-padded chunk slab prefills the prompt's NOVEL suffix
        (everything past the shared-prefix boundary) in a single dispatch.
        Only the admitted row of the (num_slots, bucket) slab is valid;
        the other rows carry in-distribution filler whose writes are
        dropped and whose tokens route to the FFF sentinel leaf."""
        self._apply_admit(slot, plan)
        L = len(req.prompt)
        sh = plan["shared_len"]
        suffix = np.asarray(req.prompt[sh:], np.int32)
        n = len(suffix)                                # >= 1 by plan
        bucket = self._bucket_for(n)
        S = self.ecfg.num_slots
        # right-pad with the LAST real token, not a constant: pad
        # positions' writes are dropped either way, but they do route
        # through FFF sites — repeating in-distribution content keeps the
        # phantom load naturally spread (same rationale as _free_tok)
        toks = np.repeat(self._free_tok[:, None], bucket, axis=1)
        toks[slot, :n] = suffix
        toks[slot, n:] = suffix[-1]
        valid = np.zeros((S,), np.int32)
        valid[slot] = n
        offs = np.zeros((S,), np.int32)
        offs[slot] = sh
        with self._ctx():
            if self.spec:
                # one dispatch prefills the suffix into BOTH cache trees
                logits, self.caches, self.draft_caches, stats, dstats = \
                    self._prefill_jits[bucket](
                        self.params, self.draft_params, jnp.asarray(toks),
                        jnp.asarray(valid), self.caches, self.draft_caches,
                        jnp.asarray(offs))
                self._stats_rows(dstats, "draft")
                self._tlen[slot] = L
                self._dlen[slot] = L
            else:
                logits, self.caches, stats = self._prefill_jits[bucket](
                    self.params, jnp.asarray(toks), jnp.asarray(valid),
                    self.caches, jnp.asarray(offs))
        logits = np.asarray(jax.block_until_ready(logits))
        self.n_prefills += 1
        self.n_prefill_tokens += n
        t = self.now()
        st = SlotState(request=req, admitted_time=t, first_token_time=t,
                       tokens=[], total_len=L, prefill_pos=L)
        self.slots[slot] = st
        # seed the slot's footprint: measured prefill counts (the admitted
        # row of the slab), else the request's hint prior
        counts = self._stats_rows(stats, "prefill")
        if counts is not None and counts[slot].sum() > 0:
            self.occupancy[slot] = counts[slot] / counts[slot].sum()
            self._measured[slot] = True
        else:
            self._measured[slot] = False
            self._seed_hint(slot, req)
        self._record_token(st, self._sample(st, logits[slot]))
        self._publish_prefix(slot)

    def _admit_chunked(self, req: Request, slot: int, plan: dict) -> None:
        """Install the page table only — no model call.  The prompt's novel
        suffix advances through the shared chunk slab in subsequent
        ``_chunk_prefill`` dispatches, starting at the shared-prefix
        boundary (``prefill_pos = shared_len`` — the shared pages' K/V are
        already in the pool)."""
        self._apply_admit(slot, plan)
        sh = plan["shared_len"]
        st = SlotState(request=req, admitted_time=self.now(),
                       first_token_time=0.0, tokens=[], total_len=0,
                       prefill_pos=sh)
        self.slots[slot] = st
        if self.spec:
            self._tlen[slot] = sh
            self._dlen[slot] = sh
        self._prefill_counts[slot] = 0.0
        self._measured[slot] = False
        self._seed_hint(slot, req)     # prior until measured counts land

    def _compact_slab(self) -> bool:
        """Whether the chunk slab holds only the prefilling slots: where the
        live FFF dispatch is exact for any token count (no capacity bound,
        or the ``exact_dense`` repair).  Under ``drop`` / ``master_leaf``
        per-leaf capacity scales with the slab's token count, so a smaller
        slab would serve other tokens: there the slab stays every slot in
        order, ``(num_slots, prefill_chunk)``."""
        return self._overflow_policy() in (None, "exact_dense")

    def _slab_rows(self, prefilling: List[int]) -> np.ndarray:
        """The chunk slab's rows: the slot of each slab row.  Compacted,
        the prefilling slots in the smallest row bucket R that holds them,
        padding rows carrying the out-of-range slot ``num_slots``; else
        every slot in order."""
        S = self.ecfg.num_slots
        if not self._compact_slab():
            return np.arange(S, dtype=np.int32)
        R = next(b for b in self._chunk_rows if b >= len(prefilling))
        rows = np.full((R,), S, np.int32)
        rows[:len(prefilling)] = prefilling
        return rows

    def _compile_chunk_family(self) -> None:
        """Compile the slab program of every row bucket the slab can take,
        running each once on padding rows alone, which write nothing: every
        later slab then reuses a compiled shape, however many slots prefill
        (DESIGN.md §9)."""
        S, C = self.ecfg.num_slots, self.ecfg.prefill_chunk
        for R in (self._chunk_rows if self._compact_slab() else (S,)):
            fn = self._chunk_jits[R] = self._chunk_factory()
            toks = jnp.full((R, C), int(self._free_tok[-1]), jnp.int32)
            zeros = jnp.zeros((R,), jnp.int32)
            rows = jnp.full((R,), S, jnp.int32)
            with self._ctx():
                if self.spec:
                    _, self.caches, self.draft_caches, _, _ = fn(
                        self.params, self.draft_params, toks, zeros,
                        self.caches, self.draft_caches, zeros, rows)
                else:
                    _, self.caches, _ = fn(self.params, toks, zeros,
                                           self.caches, zeros, rows)

    def _chunk_prefill(self) -> None:
        """One chunk slab dispatch: every mid-prefill slot consumes its next
        chunk of prompt; rows whose prompt completes sample their first
        token from the slab's logits (DESIGN.md §9).  The slab is
        ``(R, prefill_chunk)`` over the rows ``_slab_rows`` picks, one
        compiled shape per row bucket, all compiled at the first slab."""
        prefilling = [i for i, s in enumerate(self.slots)
                      if s is not None and s.prefilling]
        if not prefilling:
            return
        sp = tracing.CHUNK
        with tracing.span(sp.name):
            S, C = self.ecfg.num_slots, self.ecfg.prefill_chunk
            rows = self._slab_rows(prefilling)
            R = len(rows)
            # slab row j feeds slot rows[j]; the (row, slot) pairs with work
            work = [(j, int(i)) for j, i in enumerate(rows)
                    if i in prefilling]
            # rows without work carry in-distribution filler (same
            # rationale as the free-slot decode token); valid=0 masks their
            # writes out
            toks = np.repeat(self._free_tok[np.minimum(rows, S - 1), None],
                             C, axis=1)
            valid = np.zeros((R,), np.int32)
            offs = np.zeros((R,), np.int32)
            for j, i in work:
                st = self.slots[i]
                p = st.request.prompt
                n = min(C, len(p) - st.prefill_pos)
                toks[j, :n] = p[st.prefill_pos:st.prefill_pos + n]
                toks[j, n:] = p[st.prefill_pos + n - 1]   # pad: last real
                valid[j] = n
                offs[j] = st.prefill_pos
            if not self._chunk_jits:
                self._compile_chunk_family()
            fn = self._chunk_jits[R]
            tracing.record(self.now(), self._trace_id, tracing.SLAB_ROWS, R)
            with tracing.span(sp.dispatch), self._ctx():
                if self.spec:
                    # one slab dispatch advances every prefill in BOTH trees
                    logits, self.caches, self.draft_caches, stats, dstats = \
                        fn(self.params, self.draft_params,
                           jnp.asarray(toks), jnp.asarray(valid),
                           self.caches, self.draft_caches,
                           jnp.asarray(offs), jnp.asarray(rows))
                else:
                    logits, self.caches, stats = fn(
                        self.params, jnp.asarray(toks), jnp.asarray(valid),
                        self.caches, jnp.asarray(offs), jnp.asarray(rows))
            with tracing.span(sp.wait):
                jax.block_until_ready(logits)
            with tracing.span(sp.to_host):
                logits = np.asarray(logits)
            self.n_chunks += 1
            with tracing.span(sp.stats):
                if self.spec:
                    self._stats_rows(dstats, "draft")
                # slab overflow self-weights by real-token count: the chunk
                # validity mask routes filler positions to the sentinel
                # leaf, so RoutingStats.slots counts only valid prompt tokens
                counts = self._stats_rows(stats, "prefill")
            with tracing.span(sp.sample):
                for j, i in work:
                    st = self.slots[i]
                    st.prefill_pos += int(valid[j])
                    self.n_prefill_tokens += int(valid[j])
                    if self.spec:
                        self._tlen[i] += int(valid[j])
                        self._dlen[i] += int(valid[j])
                    if counts is not None:
                        self._prefill_counts[i] += counts[j]
                    if not st.prefilling:    # prompt fully consumed
                        self.n_prefills += 1
                        tot = self._prefill_counts[i].sum()
                        if tot > 0:
                            self.occupancy[i] = self._prefill_counts[i] / tot
                            self._measured[i] = True
                        st.total_len = len(st.request.prompt)
                        st.first_token_time = self.now()
                        self._record_token(st, self._sample(st, logits[j]))
                        self._publish_prefix(i)

    def _decode(self) -> None:
        live = [i for i, s in enumerate(self.slots)
                if s is not None and not s.done and not s.prefilling]
        if not live:
            return
        toks = self._free_tok[:, None].copy()
        offs = np.zeros((self.ecfg.num_slots,), np.int32)
        for i in live:
            st = self.slots[i]
            toks[i, 0] = st.tokens[-1]
            offs[i] = st.total_len - 1      # position of the token being fed
        # ONLY live rows write/advance their caches: mid-prefill slots must
        # not append the dummy decode token, and free/done rows' stale page
        # tables may alias pages the allocator has since handed to OTHER
        # live slots — an unmasked phantom write would corrupt them
        # (DESIGN.md §11).  Live rows' outputs are unaffected: attention is
        # row-independent and the FFF validity mask (lv) already routes
        # phantom rows to the sentinel leaf.
        wm = np.zeros((self.ecfg.num_slots,), bool)
        wm[live] = True
        # free/mid-prefill rows are phantom tokens: the validity mask routes
        # them to the FFF sentinel leaf so they never consume grouped-
        # dispatch capacity or pollute routing telemetry (DESIGN.md §9 —
        # deliberately separate from wm, which guards KV writes)
        lv = np.zeros((self.ecfg.num_slots,), bool)
        lv[live] = True
        sp = tracing.DECODE
        with tracing.span(sp.name):
            t0 = self._clock()
            with tracing.span(sp.dispatch), self._ctx(), \
                    self._decode_backend_ctx():
                logits, self.caches, stats = self._decode_jit(
                    self.params, jnp.asarray(toks), self.caches,
                    jnp.asarray(offs), jnp.asarray(wm), jnp.asarray(lv))
            with tracing.span(sp.wait):
                jax.block_until_ready(logits)
            with tracing.span(sp.to_host):
                logits = np.asarray(logits)
            t1 = self._clock()
            self.decode_lat.append(t1 - t0)
            if self._last_decode_end is not None:
                self.decode_interval_s.append(t1 - self._last_decode_end)
            self._last_decode_end = t1
            self.n_steps += 1
            with tracing.span(sp.stats):
                self._update_occupancy(live,
                                       self._stats_rows(stats, "decode"))
                self._count_decode_leaves(stats)
            with tracing.span(sp.sample):
                for i in live:
                    self._record_token(self.slots[i],
                                       self._sample(self.slots[i], logits[i]))

    def _spec_round(self) -> None:
        """One speculative draft/verify round (DESIGN.md §10), replacing
        ``_decode`` when ``spec_k > 0``.  ONE fixed-shape dispatch
        (``_spec_jit``) runs, in order:

        1. rollback — both cache trees to the host-authoritative lengths
           (undoing the previous round's rejected optimistic appends);
        2. draft rollout — ``spec_k + 1`` scanned draft decode steps with
           on-device sampling, yielding proposals + draft logits + per-slot
           draft leaf histograms;
        3. verify — the target scores the ``(num_slots, k + 1)`` slab
           ``[pending, d_1 .. d_k]`` through the chunk machinery, appending
           K/V optimistically (per-row offsets; free rows masked out of
           capacity by the validity mask, writes dropped by valid_len = 0).

        Host-side rejection sampling then emits 1 .. k + 1 tokens per live
        slot — the accepted prefix plus the corrected/bonus token — with the
        target distribution preserved exactly (greedy: the target argmax
        chain, token for token).  Draft histograms fold into the occupancy
        EWMA as an unmeasured prior, so the leaf-aware schedulers compose
        verify batches against predicted — not just trailing — leaf load.
        """
        live = [i for i, s in enumerate(self.slots)
                if s is not None and not s.done and not s.prefilling]
        if not live:
            return
        S, k = self.ecfg.num_slots, self.ecfg.spec_k
        toks = self._free_tok[:, None].copy()
        pos0 = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        lv = np.zeros((S,), bool)
        vlen = np.zeros((S,), np.int32)
        for i in live:
            st = self.slots[i]
            toks[i, 0] = st.tokens[-1]
            n = st.total_len - 1             # position of the pending token
            pos0[i] = n
            temps[i] = max(st.request.temperature, 0.0)
            lv[i] = True
            # the row's writable horizon is its ALLOCATED pages (>= prompt +
            # max_new by the admission plan), not max_len: optimistic
            # appends past the allocation would scatter into other slots'
            # pages through the clamped table lookup
            vlen[i] = min(k + 1, int(self._alloc_len[i]) - n)
        # per-step draft KV-write guards: step j appends at pos0 + j; rows
        # at their allocation edge stop writing (their later drafts go
        # unverified — vlen clips the verify slab identically)
        wm = lv[None, :] & ((pos0[None, :] + np.arange(k + 1)[:, None])
                            < self._alloc_len[None, :])
        sp = tracing.SPEC
        with tracing.span(sp.name):
            t0 = self._clock()
            with tracing.span(sp.dispatch), self._ctx():
                (drafts, q_logits, p_logits, self.caches, self.draft_caches,
                 dstats, vstats) = self._spec_jit(
                    self.params, self.draft_params, jnp.asarray(toks),
                    self.caches, self.draft_caches, jnp.asarray(self._tlen),
                    jnp.asarray(self._dlen), jnp.asarray(pos0),
                    jnp.asarray(wm), jnp.asarray(vlen), jnp.asarray(lv),
                    jnp.asarray(temps), jnp.int32(self._spec_rounds))
            self._spec_rounds += 1
            with tracing.span(sp.wait):
                jax.block_until_ready(p_logits)
            with tracing.span(sp.to_host):
                p_logits = np.asarray(p_logits)                 # (S,k+1,V)
                drafts = np.asarray(drafts)                     # (k, S)
                q_logits = np.asarray(q_logits)                 # (k+1,S,V)
            t1 = self._clock()
            self.decode_lat.append(t1 - t0)
            if self._last_decode_end is not None:
                self.decode_interval_s.append(t1 - self._last_decode_end)
            self._last_decode_end = t1
            self.n_steps += 1
            with tracing.span(sp.stats):
                # draft leaf histograms: the verify step's occupancy PRIOR.
                # Width-mismatched drafts contribute overflow telemetry only
                # (_stats_rows drops their counts); self-drafts share the
                # target's leaf space.
                self._update_occupancy(live,
                                       self._stats_rows(dstats, "draft"),
                                       measured=False)
                # verify IS the target's decode: same phase, measured
                # occupancy
                self._update_occupancy(live,
                                       self._stats_rows(vstats, "decode"))
                self._count_decode_leaves(vstats)
            with tracing.span(sp.sample):
                self._accept_drafts(live, vlen, pos0, p_logits, q_logits,
                                    drafts)

    def _accept_drafts(self, live, vlen, pos0, p_logits, q_logits,
                       drafts) -> None:
        """Host-side rejection sampling of one round: each live slot emits
        its accepted drafts plus the corrected or bonus token."""
        for i in live:
            st = self.slots[i]
            vl = int(vlen[i])
            m = vl - 1                        # drafts actually verified
            rng = None
            if st.request.temperature > 0.0:
                # 4-tuple stream: disjoint from the non-spec sampler's
                # (seed, rid, len) 3-tuples by construction
                rng = np.random.default_rng(
                    (self.ecfg.seed, st.request.rid, len(st.tokens), 2))
            emitted, n_acc = spec_lib.rejection_sample(
                p_logits[i, :vl], q_logits[:m, i], drafts[:m, i],
                st.request.temperature, rng)
            st.n_drafted += m
            st.n_accepted += n_acc
            self.n_draft_tokens += m
            self.n_accepted_tokens += n_acc
            emitted_n = 0
            for tok in emitted:
                self._record_token(st, int(tok))
                emitted_n += 1
                if st.done:   # EOS/length mid-run: later tokens never exist
                    break
            # both trees sit at pos0 + vl (optimistic appends); the slot's
            # true history is pos0 + emitted_n tokens.  Record the desired
            # lengths — the next rollout's set_cache_lengths applies them.
            self._tlen[i] = int(pos0[i]) + emitted_n
            self._dlen[i] = int(pos0[i]) + emitted_n

    def step(self) -> None:
        """One engine iteration: evict finished slots, admit from the queue,
        advance chunked prefills (up to ``prefill_budget`` slab dispatches),
        then decode every active non-prefilling slot together — one plain
        decode step, or one speculative draft/verify round when spec_k >
        0.  Each part runs in its span of ``serving/tracing.py``; the step
        records one ``engine.recompiles`` sample."""
        with tracing.span(tracing.STEP):
            n0 = sum(self.recompiles().values())
            with tracing.span(tracing.EVICT):
                self._evict_finished()
            with tracing.span(tracing.ADMIT):
                self._admit()
            if self.ecfg.prefill_chunk:
                for _ in range(self.ecfg.prefill_budget):
                    self._chunk_prefill()
            if self.spec:
                self._spec_round()
            else:
                self._decode()
            tracing.record(self.now(), self._trace_id, tracing.RECOMPILES,
                           max(sum(self.recompiles().values()) - n0, 0))

    def has_work(self) -> bool:
        """True while anything is queued or occupying a slot (the manual
        ``step()`` loop's condition)."""
        return bool(self.queue) or any(s is not None for s in self.slots)

    def run(self, requests: Sequence[Request]) -> Tuple[List[RequestResult],
                                                        metrics_lib.EngineMetrics]:
        """Serve ``requests`` (arrival_time = offsets from THIS call's start,
        seconds) to completion; returns (results sorted by rid, metrics).
        Re-entrant: each call reports only its own requests/steps and rebases
        arrivals onto its own start — ``Request.arrival_time`` offsets are
        never mutated, so the same list replays (jit caches and slot state
        persist: a later wave is a warm engine, not a fresh one)."""
        for r in requests:            # fail fast, before serving anything
            self.validate(r)
        if len({r.rid for r in requests}) != len(requests):
            raise ValueError("duplicate rids in the request batch")
        pending = deque(sorted(requests,
                               key=lambda r: (r.arrival_time, r.rid)))
        # per-run deltas against the engine-lifetime accumulators
        n_results0, n_steps0 = len(self.results), self.n_steps
        n_prefills0, n_lat0 = self.n_prefills, len(self.decode_lat)
        n_chunks0, n_int0 = self.n_chunks, len(self.decode_interval_s)
        hints0 = self._hint_mismatches
        draft0, acc0 = self.n_draft_tokens, self.n_accepted_tokens
        phit0, cow0 = self.n_prefix_hit_tokens, self.n_cow_copies
        ptoks0 = self.n_prefill_tokens
        ovf0 = {k: list(v) for k, v in self._overflow.items()}
        leaves0, rec0 = list(self._leaves), self.recompiles()
        t_start = self.now()
        self._last_decode_end = None    # decode gaps don't span runs
        while pending or self.has_work():
            while pending and t_start + pending[0].arrival_time <= self.now():
                r = pending.popleft()
                self.submit(r, arrival_time=t_start + r.arrival_time)
            if not self.has_work():
                self._last_decode_end = None    # idle gap, not a stall
                if pending:
                    wait = max(t_start + pending[0].arrival_time - self.now(),
                               0.0)
                    adv = getattr(self._clock, "advance", None)
                    if adv is not None:
                        # virtual time: jump straight to the next arrival —
                        # sleeping would stall forever on a clock that only
                        # moves when told to
                        adv(wait)
                    else:
                        time.sleep(min(wait, 0.05))
                continue
            self.step()
        elapsed = self.now() - t_start
        results = sorted(self.results[n_results0:], key=lambda r: r.rid)
        # drain this run's slice so a long-lived warm engine doesn't grow
        # without bound across waves (earlier entries belong to manual
        # step() users and are left alone)
        del self.results[n_results0:]
        lat = self.decode_lat[n_lat0:]
        del self.decode_lat[n_lat0:]
        intervals = self.decode_interval_s[n_int0:]
        del self.decode_interval_s[n_int0:]

        def ovf_delta(keys):
            w = sum(self._overflow[k][0] - ovf0[k][0] for k in keys)
            n = sum(self._overflow[k][1] - ovf0[k][1] for k in keys)
            return w / n if n else 0.0

        repairs, m_frac = self._repair_counters(ovf0)
        m = metrics_lib.from_results(
            results, elapsed_s=elapsed, n_steps=self.n_steps - n_steps0,
            n_prefills=self.n_prefills - n_prefills0,
            decode_lat_s=lat,
            overflow_mean=ovf_delta(list(self._overflow)),
            overflow_decode_mean=ovf_delta(["decode"]),
            overflow_repairs=repairs,
            master_leaf_fraction=m_frac,
            n_chunks=self.n_chunks - n_chunks0,
            decode_interval_s=intervals,
            hint_mismatches=self._hint_mismatches - hints0,
            draft_tokens=self.n_draft_tokens - draft0,
            accepted_tokens=self.n_accepted_tokens - acc0,
            prefill_tokens=self.n_prefill_tokens - ptoks0,
            prefix_hit_tokens=self.n_prefix_hit_tokens - phit0,
            cow_copies=self.n_cow_copies - cow0,
            pages_in_use=self.pool.pages_in_use,
            pages_free=self.pool.pages_free,
            decode_leaves_mean=((self._leaves[0] - leaves0[0])
                                / max(self._leaves[1] - leaves0[1], 1)),
            recompiles={k: n - rec0.get(k, 0)
                        for k, n in self.recompiles().items()})
        return results, m

    def poll_metrics(self) -> metrics_lib.EngineMetrics:
        """Live engine-lifetime telemetry snapshot — the autoscaling signal
        (ROADMAP).  Unlike ``run``'s per-run report this reflects everything
        since engine construction (or since ``run`` last drained its slice)
        plus instantaneous state: ``queue_depth`` (waiting requests),
        ``active_slots`` / ``prefilling_slots``, TTFT/latency percentiles
        over finished requests, and the overflow means.  Host-only: no
        device work, safe to call from a monitoring thread between steps.
        ``serve.py --metrics-json`` dumps the same schema (docs/serving.md
        has the field glossary)."""
        repairs, m_frac = self._repair_counters()
        m = metrics_lib.from_results(
            self.results, elapsed_s=self.now(), n_steps=self.n_steps,
            n_prefills=self.n_prefills, decode_lat_s=self.decode_lat,
            overflow_mean=self.overflow_mean(),
            overflow_decode_mean=self.overflow_mean("decode"),
            overflow_repairs=repairs,
            master_leaf_fraction=m_frac,
            n_chunks=self.n_chunks,
            decode_interval_s=self.decode_interval_s,
            hint_mismatches=self._hint_mismatches,
            draft_tokens=self.n_draft_tokens,
            accepted_tokens=self.n_accepted_tokens,
            prefill_tokens=self.n_prefill_tokens,
            prefix_hit_tokens=self.n_prefix_hit_tokens,
            cow_copies=self.n_cow_copies,
            pages_in_use=self.pool.pages_in_use,
            pages_free=self.pool.pages_free,
            decode_leaves_mean=self._leaves[0] / max(self._leaves[1], 1),
            recompiles=self.recompiles())
        m.queue_depth = len(self.queue)
        m.active_slots = sum(s is not None for s in self.slots)
        m.prefilling_slots = sum(s is not None and s.prefilling
                                 for s in self.slots)
        # live per-tenant queue depths on top of the finished-request
        # breakdown (a tenant may be all-queued with nothing finished yet)
        for t, q in self.queue.per_tenant.items():
            m.tenants.setdefault(t, {})["queue_depth"] = len(q)
        if self.profiles is not None:
            for t, snap in self.profiles.as_dict().items():
                m.tenants.setdefault(t, {})["profile"] = snap
        return m

    def occupancy_snapshot(self) -> Optional[np.ndarray]:
        """Mean leaf-occupancy EWMA across active slots — the worker's live
        FFF footprint, consumed by cluster placement (``cluster/placement``)
        to steer tenants whose learned profiles overlap it elsewhere.  None
        when the model has no FFF site; zeros when idle."""
        if not self.num_leaves:
            return None
        act = [i for i, s in enumerate(self.slots) if s is not None]
        if not act:
            return np.zeros((self.num_leaves,), np.float64)
        return self.occupancy[act].mean(axis=0)

    # -- fixed-shape accounting ----------------------------------------------

    def recompiles(self) -> Dict[str, int]:
        """Traces beyond the first, per entry point of ``compiled_shapes``:
        all 0 while the fixed-shape contract holds."""
        return {k: max(n - 1, 0) for k, n in self.compiled_shapes().items()}

    def compiled_shapes(self) -> Dict[str, int]:
        """Number of compiled traces per entry point (the fixed-shape
        contract: after warmup, decode == 1, each prefill bucket <= 1, and
        — once chunked prefill has run its first slab — exactly 1 for each
        chunk row bucket R, reported as ``prefill_chunk_<R>``)."""
        def n(fn):
            return int(fn._cache_size())
        out = {"decode": n(self._decode_jit), "admit": n(self._admit_jit)}
        for b, fn in self._prefill_jits.items():
            out[f"prefill_{b}"] = n(fn)
        for r, fn in sorted(self._chunk_jits.items()):
            out[f"prefill_chunk_{r}"] = n(fn)
        if self.spec:
            out["spec_round"] = n(self._spec_jit)
        install = getattr(self, "_cluster_install_jit", None)
        if install is not None:
            # the cluster handoff-receive dispatch (cluster/handoff.py):
            # part of a decode worker's compile family, same <= 1 contract
            out["install"] = n(install)
        return out
