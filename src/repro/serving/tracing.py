"""Span and counter names of the serving engine, and the counter log.

Spans are ``jax.profiler.TraceAnnotation``s: they land in the JAX
profiler's own trace, on the same clock as the device's operations, and
record nothing unless a profiler session is running (the cost when off is
one object and one native check per span).  The engine opens them at every
layer boundary of ``ContinuousBatchingEngine.step``:

    serve.step                   the whole of step()
      serve.evict                finished slots freed (host only)
      serve.admit                scheduler pick, page planning, admit dispatch
      serve.chunk                one chunked-prefill slab
      serve.decode               one decode step
      serve.spec                 one speculative draft/verify round
        <phase>.dispatch         the jitted call: input uploads, any trace
                                 or compile, the enqueue
        <phase>.wait             block_until_ready on the logits
        <phase>.to_host          the logits copied to the host
        <phase>.stats            routing telemetry pulled to the host
        <phase>.sample           sampling, token bookkeeping, prefix publish

where ``<phase>`` is ``serve.chunk``, ``serve.decode`` or ``serve.spec``
(a phase's span opens only when it dispatches).

Counters are kept by the program whether or not a profiler runs: a bounded
module-level log of ``(engine-clock time, engine id, name, value)``
samples, one per counter per step or per dispatch, read back with
``samples``.  The log is module-level so that a reader still finds it
after the engine is gone.
"""
from __future__ import annotations

import collections
import itertools
from typing import List, NamedTuple, Optional

import jax


class Phase(NamedTuple):
    """A dispatching phase's span name and its children's."""
    name: str
    dispatch: str
    wait: str
    to_host: str
    stats: str
    sample: str


def _phase(name: str) -> Phase:
    return Phase(name, *(f"{name}.{c}" for c in Phase._fields[1:]))


STEP = "serve.step"
EVICT = "serve.evict"
ADMIT = "serve.admit"
CHUNK = _phase("serve.chunk")
DECODE = _phase("serve.decode")
SPEC = _phase("serve.spec")
#: every span name above, children included
ALL_SPANS = (STEP, EVICT, ADMIT) + CHUNK + DECODE + SPEC

#: distinct leaves read per FFF site in one decode call (mean over sites);
#: one sample per decode step or speculative round
DECODE_LEAVES = "fff.decode_leaves"
#: rows of one chunked-prefill slab (the row bucket R); one sample per slab
SLAB_ROWS = "prefill.slab_rows"
#: traces beyond each entry point's first, summed over entry points, in
#: one step (a recompile inside serving); one sample per step
RECOMPILES = "engine.recompiles"

#: samples kept; older ones drop off (over an hour of steps at 8 steps/s)
MAX_SAMPLES = 1 << 16

_log: collections.deque = collections.deque(maxlen=MAX_SAMPLES)
_engine_ids = itertools.count()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` in the profiler's trace (a no-op when no
    profiler session is running)."""
    return jax.profiler.TraceAnnotation(name)


def new_engine_id() -> int:
    """A process-unique id for an engine's samples (ids only grow)."""
    return next(_engine_ids)


def record(t: float, engine: int, name: str, value: float) -> None:
    """Append one counter sample at engine-clock time ``t``."""
    _log.append((t, engine, name, float(value)))


def samples(name: str, t0: float, t1: float,
            engine: Optional[int] = None) -> List[float]:
    """Values of counter ``name`` recorded at engine-clock times in
    ``[t0, t1)`` by ``engine``; None means the newest engine that recorded
    ``name`` (each engine's clock starts at its construction, so samples of
    two engines are never mixed)."""
    rows = [r for r in list(_log) if r[2] == name]
    if not rows:
        return []
    if engine is None:
        engine = max(r[1] for r in rows)
    return [v for t, e, _, v in rows if e == engine and t0 <= t < t1]
