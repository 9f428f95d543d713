"""Drive the serving engine through one measured window.

Only the program's public surfaces are used: ``ContinuousBatchingEngine``
with ``submit`` / ``step`` / ``has_work`` / ``now``, its ``results``,
``decode_lat``, ``slots`` (read only, for each slot's emitted tokens and
prefill position; ``check_slot_fields`` fails set-up loudly if the fields
read are gone) and ``compiled_shapes``.  Every timestamp is on the engine's
clock.

Closed loop: every client sends its first request after warm-up, and its
next one when the last finishes.  The loop then runs untimed until the
mix's ``warm_completions`` requests have finished, so that the window opens
on a full queue whose slots are at staggered points of their requests, not
on the lock-step start.  Open loop: the window opens after warm-up; each
request is submitted at its due time (or as soon after as the loop gets to
it) and timed from when it was due.  Arrivals stop when the window closes;
requests that arrived inside it are then served until each has its first
token, for at most the mix's ``drain_s``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

WARMUP_RID = 1 << 40


@dataclasses.dataclass
class Step:
    """One ``engine.step()`` in the window: host times before and after,
    decode rows and prefill tokens it served, and the attention context
    each of them attended (for the model FLOP count)."""
    t0: float
    t1: float
    decode_rows: int
    prefill_tokens: int
    decode_ctx: int          # sum over decode rows of positions attended
    prefill_ctx: int         # sum over prefill tokens of positions attended
    emitted: int             # tokens sampled in this step (LM head rows)


@dataclasses.dataclass
class Served:
    """What the harness saw of one request."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float                    # engine clock; due time when open loop
    token_times: list = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None   # from the engine's result
    first_token: Optional[float] = None
    admitted: Optional[float] = None


@dataclasses.dataclass
class Window:
    t_first: float                    # first request sent (before t_open
                                      # when a closed loop ramps up)
    t_open: float
    t_close: float
    steps: list
    requests: dict                    # rid -> Served
    decode_lat: list                  # engine decode latencies in the window
    late_s: list                      # open loop: submitted - due
    compiles_in_window: int
    shapes_before: dict
    shapes_after: dict


def build_engine(params, program_cfg, mix: dict, seed: int, mesh=None,
                 trace_ctx=None):
    """The engine as the mix configures it; ``mesh`` and ``trace_ctx`` are
    the serving mesh (``run.serving_mesh``), None on one chip."""
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig
    e = mix["engine"]
    ecfg = EngineConfig(
        num_slots=int(e["num_slots"]), max_len=int(e["max_len"]),
        max_prompt_len=int(e["max_prompt_len"]),
        prefill_chunk=int(e["prefill_chunk"]),
        fff_backend=e.get("fff_backend", "auto"), seed=int(seed) % (1 << 31))
    return ContinuousBatchingEngine(params, program_cfg, ecfg,
                                    trace_ctx=trace_ctx, mesh=mesh)


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Compile and run every shape the window uses: admission, the chunk
    slab and the decode step, through the same calls the window makes."""
    from repro.serving.request import Request
    chunk = int(mix["engine"]["prefill_chunk"])
    rng = np.random.default_rng(0)
    for i in range(2):
        engine.submit(Request(rid=WARMUP_RID + i,
                              prompt=rng.integers(1, vocab, chunk + 3,
                                                  dtype=np.int32),
                              max_new_tokens=3))
    while engine.has_work():
        engine.step()
        check_slot_fields(engine)


#: what the harness reads of each occupied slot after a step
SLOT_FIELDS = ("request", "prefill_pos", "tokens", "first_token_time",
               "admitted_time")


def check_slot_fields(engine) -> None:
    """Fail at set-up, naming the field, if the engine's slots no longer
    carry what the window's records are read from."""
    for st in engine.slots:
        if st is None:
            continue
        missing = [f for f in SLOT_FIELDS if not hasattr(st, f)]
        if missing:
            raise RuntimeError(f"the engine's slot state has no {missing}: "
                               f"the benchmark reads them after each step")


class CompileCounter:
    """Counts the compilations JAX reports (cache hits included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1


def _snapshot(engine) -> dict:
    """rid -> (prefill_pos, tokens emitted) for every occupied slot."""
    return {st.request.rid: (st.prefill_pos, len(st.tokens), st)
            for st in engine.slots if st is not None}


def _one_step(engine, reqs: dict, steps: list, annotate) -> float:
    before = _snapshot(engine)
    t0 = engine.now()
    with annotate("engine.step"):
        engine.step()
    t1 = engine.now()
    after = _snapshot(engine)
    dec = pre = dctx = pctx = emitted = 0
    for rid, (pos1, n1, st) in after.items():
        pos0, n0, _ = before.get(rid, (0, 0, None))
        if pos1 > pos0:                       # prompt tokens consumed
            pre += pos1 - pos0
            pctx += (pos1 * (pos1 + 1) - pos0 * (pos0 + 1)) // 2
        new = n1 - n0
        if new <= 0:
            continue
        emitted += new
        P = st.request.prompt.size
        # tokens after the first come from decode rows; the k-th generated
        # token (k >= 2) is produced by feeding token k-1 at position
        # P + k - 2, which attends P + k - 1 positions
        for k in range(max(n0, 1) + 1, n1 + 1):
            dec += 1
            dctx += P + k - 1
        s = reqs.get(rid)
        if s is None:
            continue
        if n0 == 0 and n1 >= 1:
            s.first_token = st.first_token_time
            s.admitted = st.admitted_time
            s.token_times.append(st.first_token_time)
            new -= 1
        s.token_times.extend([t1] * new)
    steps.append(Step(t0, t1, dec, pre, dctx, pctx, emitted))
    return t1


def run_window(engine, mix: dict, items: list, seconds: float,
               counter: CompileCounter, annotate, on_open=None,
               on_close=None) -> Window:
    """Serve ``items`` for ``seconds``, then drain.  ``on_open`` runs just
    before the window opens and ``on_close`` just after it closes (the
    trace's start and stop); the window itself is the host span
    ``bench.window``."""
    from repro.serving.request import Request
    reqs: dict = {}
    steps: list = []
    late: list = []
    closed = mix["loop"] == "closed"
    clients = int(mix.get("clients", 0))
    queue = list(items)
    next_item = 0
    shapes_before = engine.compiled_shapes()
    n_results0 = len(engine.results)

    def submit(item, arrival):
        req = Request(rid=item.index, prompt=item.prompt,
                      max_new_tokens=item.max_new_tokens)
        engine.submit(req, arrival_time=arrival)
        reqs[item.index] = Served(item.index, item.prompt,
                                  item.max_new_tokens, arrival)

    def feed(t_limit: float) -> None:
        """Closed loop: a client whose request finished before ``t_limit``
        sends its next one, timed from the finish."""
        nonlocal next_item, seen_results
        for r in engine.results[seen_results:]:
            if r.rid in reqs and r.finish_time < t_limit \
                    and next_item < len(queue):
                submit(queue[next_item], r.finish_time)
                next_item += 1
        seen_results = len(engine.results)

    seen_results = n_results0
    t_first = engine.now()
    if closed:
        for _ in range(min(clients, len(queue))):
            submit(queue[next_item], t_first)
            next_item += 1
        warm = int(mix.get("warm_completions", 0))
        with annotate("bench.ramp"):
            while len(engine.results) - n_results0 < warm:
                _one_step(engine, reqs, [], annotate)
                feed(float("inf"))
    if on_open is not None:
        on_open()
    compiles0 = counter.n
    n_lat0 = len(engine.decode_lat)
    t_open = engine.now()
    t_close = t_open + seconds
    with annotate("bench.window"):
        while engine.now() < t_close:
            if not closed:
                now = engine.now()
                while next_item < len(queue) and \
                        t_open + queue[next_item].due <= now:
                    due = t_open + queue[next_item].due
                    submit(queue[next_item], due)
                    late.append(now - due)
                    next_item += 1
            if engine.has_work():
                _one_step(engine, reqs, steps, annotate)
            elif not closed:
                nxt = (t_open + queue[next_item].due
                       if next_item < len(queue) else t_close)
                with annotate("wait.arrival"):
                    time.sleep(max(0.0, min(nxt, t_close) - engine.now()))
            if closed:
                feed(t_close)
    if on_close is not None:
        on_close()
    compiles_in_window = counter.n - compiles0
    shapes_after = engine.compiled_shapes()
    decode_lat = list(engine.decode_lat[n_lat0:])

    # drain: serve what arrived in the window until each has a first token
    drain_until = t_close + float(mix.get("drain_s", 60))
    while engine.has_work() and engine.now() < drain_until and any(
            s.first_token is None for s in reqs.values()):
        _one_step(engine, reqs, [], annotate)
    for r in engine.results[n_results0:]:
        s = reqs.get(r.rid)
        if s is None:
            continue
        s.tokens = np.asarray(r.tokens, np.int32)
        if s.first_token is None:
            s.first_token = r.first_token_time
            s.admitted = r.admitted_time
    return Window(t_first, t_open, t_close, steps, reqs, decode_lat, late,
                  compiles_in_window, shapes_before, shapes_after)


def closed_loop_budget(mix: dict, seconds: float) -> int:
    """Requests to generate for a closed loop: far more than any window can
    finish, in whole blocks of ``clients`` (0 for an open loop)."""
    if mix["loop"] != "closed":
        return 0
    return int(mix["clients"]) * max(8, int(seconds) + 8)
