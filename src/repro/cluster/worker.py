"""Role-restricted engine wrapper + the ProcBus process entrypoint
(DESIGN.md §12).

A ``ClusterWorker`` owns one ``ContinuousBatchingEngine`` and drives it in
exactly one role:

* **prefill** — admits router-submitted requests (monolithic admit or the
  chunk slab), and the moment a slot's prompt is consumed and its first
  token sampled, ``handoff.extract``s the KV pages and releases the slot
  WITHOUT minting a result — the request leaves as a ``PrefillDone`` and
  ownership moves to a decode worker.  The engine's decode dispatch never
  runs, so a prefill worker's compile ledger is admit/chunk-slab only.
* **decode** — installs router-placed handoffs into free slots
  (``handoff.install``; an install the pool can't fund stays queued —
  backpressure the next heartbeat advertises as queue_depth) and steps the
  engine, whose queue is permanently empty: its ledger is decode (or
  spec_round) plus the single ``install`` dispatch.

The per-role split is what keeps the per-worker compile contract at the
single-engine counts (decode 1 / each chunk row bucket 1 / spec_round 1
/ admit 1): disaggregation adds processes, not compiled programs.

``worker_main`` is the ProcBus child entrypoint: module-level (picklable
by the spawn context), rebuilds params from ``(cfg, seed)`` — bit-exact,
init is deterministic — and loops inbox → handle → tick → outbox until
``Stop``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, List, Optional

from repro.cluster import bus as bus_lib
from repro.cluster import handoff as handoff_lib


@dataclasses.dataclass
class WorkerSpec:
    """Everything a spawned process needs to rebuild its engine (picklable:
    configs + seed, never params)."""
    wid: str
    role: str                      # "prefill" | "decode"
    cfg: object                    # model Config
    ecfg: object                   # EngineConfig (already role-sized)
    seed: int = 0
    heartbeat_every: int = 1
    draft_cfg: object = None       # draft model Config when spec decoding


def build_engine(spec: WorkerSpec):
    """Rebuild (params, engine) from a spec — used by ``worker_main`` and by
    LocalBus factories that want spec-identical engines in-process."""
    import jax
    from repro.models import lm
    from repro.serving.engine import ContinuousBatchingEngine

    params = lm.init_params(spec.cfg, spec.seed)
    draft = None
    if spec.draft_cfg is not None:
        draft = (lm.init(jax.random.PRNGKey(spec.seed + 1), spec.draft_cfg),
                 spec.draft_cfg)
    return params, ContinuousBatchingEngine(params, spec.cfg, spec.ecfg,
                                            draft=draft)


class ClusterWorker:
    """One engine, one role, message-driven (module docstring)."""

    def __init__(self, wid: str, role: str, engine, *,
                 heartbeat_every: int = 1,
                 failure_hook: Optional[Callable[[int], bool]] = None):
        if role not in ("prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        self.wid = wid
        self.role = role
        self.engine = engine
        self.heartbeat_every = max(1, heartbeat_every)
        self.failure_hook = failure_hook
        self.inbox: deque = deque()
        self.pending_installs: deque = deque()
        self.draining = False
        self.stopped = False
        self.n_ticks = 0
        self.handoff_bytes = 0

    # -- message handling --------------------------------------------------

    def _handle(self, msg) -> None:
        if isinstance(msg, bus_lib.Submit):
            if self.role != "prefill":
                raise ValueError(f"{self.wid}: decode worker got Submit")
            self.engine.submit(msg.req)
        elif isinstance(msg, bus_lib.Install):
            if self.role != "decode":
                raise ValueError(f"{self.wid}: prefill worker got Install")
            self.pending_installs.append(msg.handoff)
        elif isinstance(msg, bus_lib.Drain):
            self.draining = True
        elif isinstance(msg, bus_lib.Stop):
            self.stopped = True
        else:
            raise ValueError(f"{self.wid}: unknown message {type(msg)}")

    def _heartbeat(self) -> bus_lib.Heartbeat:
        e = self.engine
        occ = e.occupancy_snapshot()
        profiles = e.profiles.as_dict() if e.profiles is not None else None
        return bus_lib.Heartbeat(
            wid=self.wid, role=self.role, t=e.now(), n_ticks=self.n_ticks,
            pages_free=e.pool.pages_free, pages_total=e.pool.num_pages,
            queue_depth=len(e.queue) + len(self.pending_installs),
            active_slots=sum(s is not None for s in e.slots),
            num_slots=e.ecfg.num_slots, occupancy=occ, profiles=profiles,
            compiled_shapes=e.compiled_shapes(),
            handoff_bytes=self.handoff_bytes, draining=self.draining)

    @property
    def idle(self) -> bool:
        return (not self.engine.has_work() and not self.pending_installs
                and not self.inbox)

    # -- the tick ----------------------------------------------------------

    def tick(self) -> List[object]:
        """Drain inbox, advance the engine one step for this role, return
        the outbound messages.  Raises WorkerKilled when the failure hook
        fires — LocalBus turns that into a dropped worker."""
        if self.stopped:
            return []
        out: List[object] = []
        while self.inbox:
            self._handle(self.inbox.popleft())
            if self.stopped:
                out.append(bus_lib.Bye(self.wid,
                                       self.engine.compiled_shapes(),
                                       {"n_ticks": self.n_ticks,
                                        "handoff_bytes": self.handoff_bytes}))
                return out
        self.n_ticks += 1
        if self.failure_hook is not None and self.failure_hook(self.n_ticks):
            raise bus_lib.WorkerKilled(self.wid)
        if self.role == "decode":
            self._tick_decode()
        else:
            out.extend(self._tick_prefill())
        for r in self.engine.results:
            out.append(bus_lib.Done(self.wid, r))
        del self.engine.results[:]
        if self.n_ticks % self.heartbeat_every == 0:
            out.append(self._heartbeat())
        if self.draining and self.idle:
            out.append(bus_lib.Drained(self.wid))
            self.draining = False          # report once; router stops us
        return out

    def _tick_decode(self) -> None:
        while self.pending_installs:
            slot = handoff_lib.install(self.engine,
                                       self.pending_installs[0])
            if slot is None:
                break                       # no slot/pages yet: backpressure
            self.handoff_bytes += self.pending_installs.popleft().nbytes
        self.engine.step()                  # queue empty: decode/evict only

    def _tick_prefill(self) -> List[object]:
        e = self.engine
        e._evict_finished()
        if not self.draining:
            e._admit()                      # monolithic: full prefill here
        if e.ecfg.prefill_chunk:
            for _ in range(e.ecfg.prefill_budget):
                e._chunk_prefill()
        out: List[object] = []
        for i, st in enumerate(e.slots):
            if st is None or st.prefilling or not st.tokens:
                continue
            if st.done:
                continue                    # finished at prefill: evict path
            h = handoff_lib.extract(e, i)
            e.release_slot(i, record_result=False)
            out.append(bus_lib.PrefillDone(self.wid, h))
        return out


def worker_main(spec: WorkerSpec, inbox, outbox) -> None:
    """ProcBus child entrypoint: rebuild the engine, serve messages until
    ``Stop`` (or SIGKILL, which needs no goodbye)."""
    import queue as queue_lib

    _, engine = build_engine(spec)
    worker = ClusterWorker(spec.wid, spec.role, engine,
                           heartbeat_every=spec.heartbeat_every)
    while not worker.stopped:
        try:
            if worker.idle:
                worker.inbox.append(inbox.get(timeout=0.02))
            while True:
                worker.inbox.append(inbox.get_nowait())
        except queue_lib.Empty:
            pass
        for msg in worker.tick():       # tick emits the Bye on Stop
            outbox.put(msg)
