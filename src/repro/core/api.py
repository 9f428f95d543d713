"""The single FFF entry point: ``apply()`` + a pluggable execution-backend
registry (DESIGN.md §2).

The paper's layer has one contract and many execution strategies: FORWARD_T's
soft mixture for training, FORWARD_I's log-time hard descent for inference,
and — per strategy — a pure-gather reference, a capacity-bounded grouped
dispatch (SPMD-shardable), an expert-parallel shard_map/all_to_all path
(``grouped_ep``, DESIGN.md §5) and the Pallas TPU kernels.  Every consumer
goes through::

    y, out = api.apply(params, cfg, x, api.ExecutionSpec(mode="infer"))

``ExecutionSpec.backend`` names the implementation; ``"auto"`` (the default)
picks one from the platform, token count, tree depth and config.  All
backends return the same ``(y, FFFOutput)`` pair, so swapping execution
strategies (new kernels, sharded backends, batching policies) never touches
call sites.

Adding a backend::

    def my_backend(params, cfg, x, spec):
        ...
        return y, api.FFFOutput(leaf_idx=idx)

    api.register_backend("infer", "mine", my_backend)
    y, out = api.apply(params, cfg, x,
                       api.ExecutionSpec(mode="infer", backend="mine"))

The launch layer can steer ``backend="auto"`` call sites wholesale with
``with api.overrides(backend="grouped"): ...`` (same thread-local pattern
as ``repro.distributed.act.use_mesh`` — read at trace time).  The same
context manager composes every trace-time override — backend, capacity
factor and overflow policy — and nests (inner wins per field); the old
single-purpose ``use_backend`` / ``use_capacity_factor`` /
``use_overflow_policy`` names survive as thin deprecated aliases.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import utils
from repro.core import fff as fff_lib
from repro.distributed import act as dist_act

MODES = ("train", "infer")

#: pre-registry capacity defaults, preserved per backend (ExecutionSpec's
#: capacity_factor=None means "use the backend's own default")
DEFAULT_CAPACITY_TRAIN_ST = 1.5
DEFAULT_CAPACITY_INFER = 2.0
#: grouped_ep runs Switch-style tight capacity: every slot crosses the wire
#: twice (all_to_all there and back), and exactness comes from the
#: overflow-to-dense repair, not headroom (DESIGN.md §5/§8)
DEFAULT_CAPACITY_EP = 1.25

#: token count at or below which the pallas backend prefers the per-token
#: gathered decode kernel over the sorted-dispatch grouped GEMM (DESIGN.md §3)
PALLAS_DECODE_MAX_TOKENS = 32

#: what happens to tokens a capacity-bounded backend drops (DESIGN.md §14):
#: "exact_dense" repairs them with the per-token dense fallback (exact,
#: all_gather traffic under EP), "master_leaf" lets the always-on master-leaf
#: term stand in (approximate, zero repair traffic, needs cfg.master_leaf),
#: "drop" leaves them at zero output (historical grouped behaviour)
OVERFLOW_POLICIES = ("exact_dense", "master_leaf", "drop")


def default_capacity_factor(backend: str, mode: str = "infer") -> float:
    """The capacity factor a capacity-bounded backend runs with when
    ``ExecutionSpec.capacity_factor`` is None — the single source of truth
    for consumers that must PREDICT dispatch behavior (e.g. the serving
    scheduler's overflow proxy, DESIGN.md §9)."""
    if mode == "train":
        return DEFAULT_CAPACITY_TRAIN_ST
    return DEFAULT_CAPACITY_EP if backend == "grouped_ep" \
        else DEFAULT_CAPACITY_INFER


def default_overflow_policy(backend: str) -> str:
    """The overflow policy a capacity-bounded backend runs with when
    ``ExecutionSpec.overflow_policy`` is None — the historical per-backend
    behaviour the first-class policy replaced (DESIGN.md §14): grouped_ep
    repaired exactly, grouped dropped.  Exact backends have no overflow, so
    the answer only matters for capacity-bounded ones; consumers that must
    predict repair behaviour (serving metrics, ``dispatch.ep_bytes_moved``)
    read it from here."""
    return "exact_dense" if backend == "grouped_ep" else "drop"

#: per-tree training width at which "auto" inference switches from the exact
#: per-token gather to capacity-bounded grouped dispatch (DESIGN.md §3)
AUTO_GROUPED_MIN_WIDTH = 4096


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """How to execute one FFF layer application.

    mode:            "train" (FORWARD_T semantics) | "infer" (FORWARD_I)
    backend:         registered backend name, or "auto" to resolve from the
                     platform, token count, depth and config
    capacity_factor: per-leaf capacity multiplier for capacity-bounded
                     backends (grouped dispatch, pallas leaf GEMM); None =
                     each backend's own default (1.5 for ST training, 2.0
                     for serving — the pre-registry values)
    overflow_policy: what a capacity-bounded backend does with tokens it
                     drops — one of ``OVERFLOW_POLICIES`` ("exact_dense" |
                     "master_leaf" | "drop", DESIGN.md §14); None = the
                     backend's historical default
                     (``default_overflow_policy``: "exact_dense" for
                     grouped_ep, "drop" for grouped).  "master_leaf"
                     requires ``cfg.master_leaf`` — the always-on master
                     term is what stands in for the dropped leaf output.
                     Exact (capacity-unbounded) backends ignore it.
    dense_levels:    tree levels routed by one dense logit matmul before
                     falling back to per-token gathers (DESIGN.md §3)
    rng:             PRNG key for stochastic training features (child
                     transposition); unused by inference backends
    interpret:       Pallas interpret-mode override (None = autodetect:
                     interpret everywhere but TPU)
    valid:           optional boolean per-token validity mask, broadcastable
                     to x's leading (batch, ...) shape.  Capacity-bounded
                     backends route invalid tokens to the capacity-neutral
                     sentinel leaf so phantom rows (e.g. a serving engine's
                     free slots) never consume grouped-dispatch capacity or
                     appear in routing telemetry, and exclude them from
                     overflow accounting.  Exact backends (reference,
                     pallas, and grouped_ep's overflow repair) ignore it —
                     their outputs are per-token exact regardless.
    """
    mode: str = "infer"
    backend: str = "auto"
    capacity_factor: Optional[float] = None
    overflow_policy: Optional[str] = None
    dense_levels: int = 8
    rng: Optional[jax.Array] = None
    interpret: Optional[bool] = None
    valid: Optional[jax.Array] = None

    def validate(self) -> "ExecutionSpec":
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if (self.overflow_policy is not None
                and self.overflow_policy not in OVERFLOW_POLICIES):
            raise ValueError(
                f"overflow_policy must be one of {OVERFLOW_POLICIES} or None, "
                f"got {self.overflow_policy!r}")
        return self


@dataclasses.dataclass(frozen=True)
class FFFOutput:
    """Structured aux returned by every backend.  Fields a backend cannot
    produce are None — e.g. hard inference has no node probabilities, and
    exact (capacity-unbounded) paths report no overflow.

    leaf_idx:          (..., trees) int32 — routed leaf per (token, tree)
    node_probs:        (B, trees, num_nodes) — sigmoid node outputs
    mixture:           (B, trees, num_leaves) — FORWARD_T leaf weights
    entropy:           scalar — mean Bernoulli entropy of node decisions
    overflow_fraction: scalar — fraction of (token, tree) slots dropped by a
                       capacity bound (0 for exact paths)
    """
    leaf_idx: Optional[jax.Array] = None
    node_probs: Optional[jax.Array] = None
    mixture: Optional[jax.Array] = None
    entropy: Optional[jax.Array] = None
    overflow_fraction: Optional[jax.Array] = None

    def as_dict(self) -> dict:
        """Legacy aux-dict view (the pre-registry forward_* return type).
        References the field arrays, no copies (asdict would deep-copy)."""
        return {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
                if getattr(self, f.name) is not None}


jax.tree_util.register_dataclass(
    FFFOutput,
    data_fields=["leaf_idx", "node_probs", "mixture", "entropy",
                 "overflow_fraction"],
    meta_fields=[])


@dataclasses.dataclass(frozen=True)
class RoutingStats:
    """Per-call routing telemetry for serving observability (DESIGN.md §9).

    Built by ``routing_stats_from`` out of the ``FFFOutput`` every backend
    already returns, when a ``collect_routing()`` tap is active.  The serving
    engine's scheduler consumes these to compose microbatches that balance
    leaf load (the paper's grouped dispatch is composition-sensitive:
    capacity overflow depends on which tokens share a batch).

    leaf_counts: (B, E) float32 — routed (token, tree) slots per leading
                 batch row per leaf, summed over every other leading axis
                 (sequence) and trees.  Row b is batch element b's *leaf
                 footprint* at this site.
    overflow:    scalar — the call's overflow_fraction (0 for exact paths)
    slots:       scalar — total routed (token, tree) slots (weight for
                 averaging overflow across sites)
    distinct:    scalar — leaves with a non-zero count over the call's valid
                 rows: the leaves the call read.  For a forest it counts
                 distinct leaf indices across trees, a lower bound on the
                 distinct (tree, leaf) pairs read.  Summed over sites like
                 ``slots`` when a layer scan stacks them.
    """
    leaf_counts: jax.Array
    overflow: jax.Array
    slots: jax.Array
    distinct: jax.Array


jax.tree_util.register_dataclass(
    RoutingStats, data_fields=["leaf_counts", "overflow", "slots",
                               "distinct"],
    meta_fields=[])


@contextlib.contextmanager
def collect_routing(enable: bool = True):
    """Ask FFF call sites to surface ``RoutingStats`` for the dynamic extent
    of a trace.  Read at trace time (same thread-local pattern as
    ``use_backend``): model code checks ``routing_enabled()`` and, when true,
    attaches ``routing_stats_from(out, cfg)`` to its aux outputs so the
    telemetry rides the normal function returns — it must, because inside a
    ``lax.scan`` over layers a side-channel list would capture scan-body
    tracers that cannot escape the loop."""
    prev = getattr(_thread_state, "routing", False)
    _thread_state.routing = bool(enable)
    try:
        yield
    finally:
        _thread_state.routing = prev


def routing_enabled() -> bool:
    """Whether a ``collect_routing()`` tap is active for the current trace."""
    return bool(getattr(_thread_state, "routing", False))


def routing_stats_from(out: FFFOutput, cfg: "fff_lib.FFFConfig",
                       valid: Optional[jax.Array] = None
                       ) -> Optional[RoutingStats]:
    """Compact per-call telemetry from a backend's ``FFFOutput``.

    Reduces ``leaf_idx`` (B, ..., trees) to a per-batch-row leaf histogram
    (B, E); returns None when the backend reported no leaf indices (e.g.
    FORWARD_T training, depth-0 sites).  ``valid`` (broadcastable to the
    call's token shape, ``leaf_idx.shape[:-1]``) marks the tokens whose
    leaves ``distinct`` counts: exact backends report phantom rows' real
    leaves, which stay in ``leaf_counts`` as before."""
    if out.leaf_idx is None:
        return None
    idx = out.leaf_idx
    if idx.ndim == 1:                      # (B,) single-tree flat call
        idx = idx[:, None]
    flat = idx.reshape(idx.shape[0], -1)   # (B, S*...*trees)
    counts = jax.vmap(
        lambda i: jnp.bincount(i, length=cfg.num_leaves))(flat)
    counts = counts.astype(jnp.float32)
    ovf = (out.overflow_fraction if out.overflow_fraction is not None
           else jnp.zeros((), jnp.float32))
    read = idx
    if valid is not None:                  # the sentinel id is not counted
        read = jnp.where(jnp.broadcast_to(valid, idx.shape[:-1])[..., None],
                         idx, cfg.num_leaves)
    distinct = (jnp.bincount(read.reshape(-1), length=cfg.num_leaves) > 0)
    return RoutingStats(leaf_counts=counts, overflow=ovf,
                        slots=counts.sum(),
                        distinct=distinct.sum().astype(jnp.float32))

BackendFn = Callable[[dict, "fff_lib.FFFConfig", jax.Array, ExecutionSpec],
                     tuple[jax.Array, FFFOutput]]
SupportsFn = Callable[[dict, "fff_lib.FFFConfig"], bool]

_REGISTRY: dict[tuple[str, str], BackendFn] = {}
_SUPPORTS: dict[tuple[str, str], SupportsFn] = {}
_thread_state = threading.local()


def register_backend(mode: str, name: str, fn: BackendFn,
                     supports: Optional[SupportsFn] = None) -> None:
    """Register ``fn`` as execution backend ``name`` for ``mode``.

    ``fn(params, cfg, x, spec) -> (y, FFFOutput)`` with ``x`` (..., dim_in)
    and ``y`` (..., dim_out).  ``supports(params, cfg) -> bool`` (optional)
    is the eligibility predicate the *auto* resolver honours — both when
    picking the backend itself and when a ``use_backend`` override names it;
    ineligible configs fall through to the heuristics instead of crashing
    inside the backend.  Explicit ``ExecutionSpec(backend=name)`` bypasses
    it: explicit means explicit, and the backend's own errors apply.
    Re-registering a name overwrites it (so tests and downstream packages
    can shadow the built-ins)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if name == "auto":
        raise ValueError('"auto" is the resolver, not a registrable backend')
    _REGISTRY[(mode, name)] = fn
    if supports is not None:
        _SUPPORTS[(mode, name)] = supports
    else:
        _SUPPORTS.pop((mode, name), None)


def _backend_supported(mode: str, name: str, params: dict,
                       cfg: fff_lib.FFFConfig) -> bool:
    pred = _SUPPORTS.get((mode, name))
    return pred is None or pred(params, cfg)


def get_backend(mode: str, name: str) -> BackendFn:
    try:
        return _REGISTRY[(mode, name)]
    except KeyError:
        raise KeyError(
            f"no backend {name!r} registered for mode {mode!r}; available: "
            f"{list_backends(mode)}") from None


def list_backends(mode: Optional[str] = None) -> list[str]:
    """Registered backend names, optionally restricted to one mode."""
    if mode is None:
        return sorted({n for _, n in _REGISTRY})
    return sorted(n for m, n in _REGISTRY if m == mode)


def overrides(*, backend: Optional[str] = None, mode: Optional[str] = None,
              capacity_factor: Optional[float] = None,
              overflow_policy: Optional[str] = None):
    """One composable trace-time override context for ``apply()`` (DESIGN.md
    §2/§14): steer ``backend="auto"`` resolution, fill in unset
    ``capacity_factor``s, and fill in unset ``overflow_policy``s — any
    subset at once, for the dynamic extent of a trace in this thread.

    ``backend`` steers every ``backend="auto"`` apply() to the named
    implementation; explicit non-auto specs are unaffected.  ``mode``
    restricts the backend override to one mode — pass ``mode="infer"`` when
    a name exists for both modes with different math (``"grouped"`` is exact
    dispatch for inference but the ST top-1 *estimator* for training; an
    unrestricted override would silently change training semantics).
    Backends missing for an applicable mode — or failing their registered
    ``supports`` predicate for a given (params, cfg) — fall through to the
    normal auto heuristics, so e.g. ``overrides(backend="pallas")`` serves
    kernel-eligible inference sites with the kernels while biased-leaf sites
    and training keep their normal paths.  A name registered for no mode at
    all raises up front — otherwise a typo would silently run auto.

    ``capacity_factor`` fills in every spec that leaves its own unset;
    explicit per-spec values win.  The motivating consumer is the serving
    engine's speculative verify dispatch (DESIGN.md §10): a verify slab is
    k+1 decode steps fused onto one token axis, so its per-leaf capacity
    must scale with that axis — otherwise speculation would *change serving
    numerics* instead of just batching them.  Capacity-free exact backends
    ignore capacity factors entirely, so the override is harmless there.

    ``overflow_policy`` (one of ``OVERFLOW_POLICIES``) likewise fills in
    specs that leave theirs unset — how the serving engine selects
    master-leaf overflow repair for a whole trace without touching call
    sites.

    Contexts nest: each ``overrides()`` saves and restores exactly the
    fields it sets, so an inner context wins per field and unrelated fields
    compose (``overrides(backend=...)`` inside
    ``overrides(capacity_factor=...)`` leaves the capacity override
    active).  Validation is eager — bad arguments raise at the call, before
    the ``with`` body runs."""
    if mode is not None and mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode is not None and backend is None:
        raise ValueError("mode= only restricts a backend override; pass "
                         "backend= as well")
    if backend is not None and not any(n == backend for _, n in _REGISTRY):
        raise KeyError(f"no backend {backend!r} registered for any mode; "
                       f"available: {list_backends()}")
    if capacity_factor is not None:
        capacity_factor = float(capacity_factor)
        if capacity_factor <= 0:
            raise ValueError(
                f"capacity factor must be positive, got {capacity_factor}")
    if overflow_policy is not None and overflow_policy not in OVERFLOW_POLICIES:
        raise ValueError(f"overflow_policy must be one of {OVERFLOW_POLICIES},"
                         f" got {overflow_policy!r}")

    sets = []
    if backend is not None:
        sets.append(("override", (backend, mode)))
    if capacity_factor is not None:
        sets.append(("capacity_override", capacity_factor))
    if overflow_policy is not None:
        sets.append(("overflow_override", overflow_policy))

    @contextlib.contextmanager
    def _installed():
        prev = [(a, getattr(_thread_state, a, None)) for a, _ in sets]
        for a, v in sets:
            setattr(_thread_state, a, v)
        try:
            yield
        finally:
            for a, v in prev:
                setattr(_thread_state, a, v)

    return _installed()


def _deprecated_alias(old: str, new: str) -> None:
    warnings.warn(f"api.{old} is deprecated; use api.{new}",
                  DeprecationWarning, stacklevel=3)


def use_backend(name: str, mode: Optional[str] = None):
    """Deprecated alias for ``overrides(backend=name, mode=mode)``."""
    _deprecated_alias("use_backend(name)", "overrides(backend=name)")
    return overrides(backend=name, mode=mode)


def use_capacity_factor(cf: float):
    """Deprecated alias for ``overrides(capacity_factor=cf)``."""
    _deprecated_alias("use_capacity_factor(cf)", "overrides(capacity_factor=cf)")
    return overrides(capacity_factor=cf)


def use_overflow_policy(policy: str):
    """Deprecated alias for ``overrides(overflow_policy=policy)``."""
    _deprecated_alias("use_overflow_policy(policy)",
                      "overrides(overflow_policy=policy)")
    return overrides(overflow_policy=policy)


def _pallas_supported(params: dict, cfg: fff_lib.FFFConfig) -> bool:
    """The kernel path collapses the node net to one hyperplane and needs the
    zero-row padding invariant of bias-free leaves (kernels/leaf_gemm)."""
    return (cfg.node_width == 1 and "leaf_b1" not in params
            and "leaf_b2" not in params)


def _kernels_native() -> bool:
    """Whether Pallas kernels compile natively here (TPU).  The interpret
    fallback keeps them *correct* everywhere, but auto never picks an
    interpreted kernel over a compiled XLA path — tests monkeypatch this to
    exercise the kernel branches of the resolver on CPU."""
    return jax.default_backend() == "tpu"


def _resolve_auto(params: dict, cfg: fff_lib.FFFConfig, mode: str,
                  x_shape: Optional[tuple] = None) -> str:
    """Backend choice for ``backend="auto"`` (DESIGN.md §3 regime map):

    train: the ST-grouped estimator when the config asks for it (MoE-scale
           sites) and there is a tree to descend; otherwise faithful
           FORWARD_T.
    infer: expert-parallel a2a dispatch (grouped_ep) whenever a mesh with a
           model axis >1 is installed and the leaf count divides over it —
           sharded serving's whole point is that tokens travel to the leaf
           shards (§5); else, on TPU with a kernel-eligible config: the
           fused decode MEGAKERNEL (``pallas_decode``, §13) for seq-len-1
           shapes — serving decode's forever-shape — and the three-kernel
           ``pallas`` path otherwise; grouped dispatch for wide sites —
           always, regardless of token count, because wide sites are the
           EP-sharded ones and the per-token gather would allgather their
           sharded leaf weights; the exact gather reference otherwise
           (small sites, depth 0).  ``x_shape`` is the call's input shape
           when known (apply() passes it); shape-blind resolution simply
           never picks the decode-shaped fast path."""
    override = getattr(_thread_state, "override", None)
    if override is not None:
        o_name, o_mode = override
        if ((o_mode in (None, mode)) and (mode, o_name) in _REGISTRY
                and _backend_supported(mode, o_name, params, cfg)):
            return o_name
    if mode == "train":
        return "grouped" if (cfg.st_training and cfg.depth > 0) else "reference"
    if cfg.depth == 0:
        return "reference"
    if (dist_act.model_shard_count() > 1
            and _backend_supported("infer", "grouped_ep", params, cfg)):
        return "grouped_ep"
    if (x_shape is not None and len(x_shape) >= 3 and x_shape[-2] == 1
            and _kernels_native()
            and _backend_supported("infer", "pallas_decode", params, cfg)):
        return "pallas_decode"
    if (_kernels_native()
            and _backend_supported("infer", "pallas", params, cfg)):
        return "pallas"
    if cfg.num_leaves * cfg.leaf_width >= AUTO_GROUPED_MIN_WIDTH:
        return "grouped"
    return "reference"


def resolve_backend(params: dict, cfg: "fff_lib.FFFConfig",
                    mode: str = "infer",
                    x_shape: Optional[tuple] = None) -> str:
    """The backend ``apply(backend="auto")`` would run under the CURRENT
    trace-time context (installed mesh, ``use_backend`` override, supports
    predicates) — for consumers that must predict dispatch behavior without
    running it, e.g. the serving scheduler's capacity proxy (DESIGN.md §9).
    Pass the site's params when available; ``{}`` is an acceptable proxy for
    bias-free configs (the predicates only probe bias keys).  ``x_shape``
    (the ``(..., seq, dim)`` input shape) enables the shape-dependent picks
    — without it the decode-shaped fast path is never predicted."""
    return _resolve_auto(params, cfg, mode, x_shape=x_shape)


def apply(params: dict, cfg: fff_lib.FFFConfig, x: jax.Array,
          spec: ExecutionSpec = ExecutionSpec()
          ) -> tuple[jax.Array, FFFOutput]:
    """Apply one FFF layer: x (..., dim_in) -> (..., dim_out), FFFOutput.

    The only supported invocation of the layer outside ``repro.core``; the
    backend registry does the rest (module docstring has the map).

    When ``cfg.master_leaf`` is set the always-on master-leaf term
    (``fff.master_apply``, DESIGN.md §14) is added HERE, after backend
    dispatch, so every backend — reference, grouped, grouped_ep, pallas —
    gets identical master semantics without per-backend code and without an
    extra pallas_call (the addition is plain jnp and fuses into the
    surrounding XLA program).  The one exception is the fused decode
    megakernel, which folds the master MLP into its single kernel."""
    cf = getattr(_thread_state, "capacity_override", None)
    if cf is not None and spec.capacity_factor is None:
        spec = dataclasses.replace(spec, capacity_factor=cf)
    op = getattr(_thread_state, "overflow_override", None)
    if op is not None and spec.overflow_policy is None:
        spec = dataclasses.replace(spec, overflow_policy=op)
    spec.validate()
    if spec.overflow_policy == "master_leaf" and not cfg.master_leaf:
        raise ValueError(
            'overflow_policy="master_leaf" requires cfg.master_leaf=True — '
            "without the always-on master term, dropped tokens would "
            'silently degrade to zeros (use "drop" to ask for that)')
    name = spec.backend
    if name == "auto":
        name = _resolve_auto(params, cfg, spec.mode, x_shape=x.shape)
    y, out = get_backend(spec.mode, name)(params, cfg, x, spec)
    if cfg.master_leaf and not (name == "pallas_decode" and cfg.depth > 0):
        y = y + fff_lib.master_apply(params, cfg, x).astype(y.dtype)
    return y, out


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

def _train_reference(params, cfg, x, spec):
    """FORWARD_T: dense soft mixture over all leaves (paper Algorithm 1)."""
    y, aux = fff_lib._forward_soft_mixture(params, cfg, x, rng=spec.rng)
    return y, FFFOutput(node_probs=aux["node_probs"], mixture=aux["mixture"],
                        entropy=aux["entropy"])


def _train_grouped(params, cfg, x, spec):
    """Straight-through top-1 training via capacity-bounded grouped dispatch
    (O(l) leaf cost per token; DESIGN.md §8)."""
    cf = (spec.capacity_factor if spec.capacity_factor is not None
          else DEFAULT_CAPACITY_TRAIN_ST)
    y, aux = fff_lib._forward_st_grouped(
        params, cfg, x, rng=spec.rng, capacity_factor=cf)
    return y, FFFOutput(leaf_idx=aux["leaf_idx"],
                        node_probs=aux["node_probs"], mixture=aux["mixture"],
                        entropy=aux["entropy"],
                        overflow_fraction=aux["overflow_fraction"])


def _infer_reference(params, cfg, x, spec):
    """FORWARD_I: hard descent + exact per-token leaf gather."""
    y, aux = fff_lib._forward_hard_gather(params, cfg, x,
                                          dense_levels=spec.dense_levels)
    return y, FFFOutput(leaf_idx=aux["leaf_idx"],
                        overflow_fraction=jnp.zeros((), jnp.float32))


def _infer_grouped(params, cfg, x, spec):
    """FORWARD_I via capacity-bounded grouped dispatch (EP-shardable).
    ``spec.overflow_policy`` governs dropped tokens (default "drop",
    the historical behaviour; DESIGN.md §14)."""
    cf = (spec.capacity_factor if spec.capacity_factor is not None
          else DEFAULT_CAPACITY_INFER)
    policy = (spec.overflow_policy if spec.overflow_policy is not None
              else default_overflow_policy("grouped"))
    y, aux = fff_lib._forward_hard_grouped(
        params, cfg, x, capacity_factor=cf, dense_levels=spec.dense_levels,
        valid=spec.valid, overflow_policy=policy)
    return y, FFFOutput(leaf_idx=aux["leaf_idx"],
                        overflow_fraction=aux["overflow_fraction"])


def _infer_grouped_ep(params, cfg, x, spec):
    """FORWARD_I via expert-parallel shard_map + all_to_all dispatch
    (DESIGN.md §5).  Leaf weights stay sharded on the model axis; tokens
    travel to their routed leaf's shard and back.  Exact under the default
    ``overflow_policy="exact_dense"``: over-capacity tokens take the
    overflow-to-dense repair, and overflow_fraction reports the true repair
    rate.  "master_leaf"/"drop" (§14) omit the repair round — and its
    all_gather traffic — entirely.  Degrades to local grouped dispatch +
    the same policy when no mesh is installed (so the contract is testable
    unsharded)."""
    cf = (spec.capacity_factor if spec.capacity_factor is not None
          else DEFAULT_CAPACITY_EP)
    policy = (spec.overflow_policy if spec.overflow_policy is not None
              else default_overflow_policy("grouped_ep"))
    y, aux = fff_lib._forward_hard_ep(
        params, cfg, x, capacity_factor=cf, dense_levels=spec.dense_levels,
        valid=spec.valid, overflow_policy=policy)
    return y, FFFOutput(leaf_idx=aux["leaf_idx"],
                        overflow_fraction=aux["overflow_fraction"])


def _infer_pallas(params, cfg, x, spec):
    """FORWARD_I on the Pallas TPU kernels: fused tree-router descent, then
    sorted-dispatch grouped GEMMs (batch) or per-token gathered matmuls
    (decode-sized batches).  Exact: grouped overflow falls back to the dense
    gather (DESIGN.md §8), so overflow_fraction is 0 by construction."""
    # imported here, not at module scope: repro.kernels sits above repro.core
    # in the layering and itself imports this package
    from repro.kernels.fused_fff import ops as fused_ops
    from repro.kernels.leaf_gemm import ops as gemm_ops
    xf, lead = utils.flatten_leading(x)
    if xf.shape[0] <= PALLAS_DECODE_MAX_TOKENS:
        y, leaf_idx = fused_ops.fff_decode(
            xf, params, cfg, interpret=spec.interpret,
            dense_levels=spec.dense_levels, return_leaf_idx=True)
    else:
        cf = (spec.capacity_factor if spec.capacity_factor is not None
              else DEFAULT_CAPACITY_INFER)
        y, leaf_idx = gemm_ops.fff_infer(
            xf, params, cfg, capacity_factor=cf,
            interpret=spec.interpret, dense_levels=spec.dense_levels,
            return_leaf_idx=True)
    return (utils.unflatten_leading(y, lead),
            FFFOutput(leaf_idx=utils.unflatten_leading(leaf_idx, lead),
                      overflow_fraction=jnp.zeros((), jnp.float32)))


def _infer_pallas_decode(params, cfg, x, spec):
    """FORWARD_I on the fused decode MEGAKERNEL (DESIGN.md §13): tree
    routing, the selected leaf's MLP and the forest combine in ONE
    ``pl.pallas_call`` — built for the serving engine's ``(num_slots, 1)``
    decode shape, where the three-dispatch pallas path pays two extra
    kernel launches and an HBM round trip of the hidden activation per
    token.  Exact for any batch (per-token, no capacity bound), so
    ``spec.valid`` does not change outputs; it only masks the reported
    ``leaf_idx`` to the sentinel leaf so phantom rows (a serving engine's
    free slots) stay out of routing telemetry — ``routing_stats_from``'s
    bincount drops the sentinel id, same contract as the capacity-bounded
    backends (DESIGN.md §9)."""
    if cfg.depth == 0:
        # a depth-0 FFF is one dense leaf: no tree to descend, nothing to
        # fuse.  The supports predicate keeps auto away from this case;
        # an explicit request stays correct via the reference path.
        return _infer_reference(params, cfg, x, spec)
    # imported here, not at module scope: repro.kernels sits above repro.core
    # in the layering and itself imports this package
    from repro.kernels.fused_decode import ops as fd_ops
    xf, lead = utils.flatten_leading(x)
    y, leaf_idx = fd_ops.fused_decode(xf, params, cfg,
                                      interpret=spec.interpret,
                                      return_leaf_idx=True)
    if spec.valid is not None:
        vf = jnp.broadcast_to(spec.valid, x.shape[:-1]).reshape(-1)
        leaf_idx = jnp.where(vf[:, None], leaf_idx, cfg.num_leaves)
    return (utils.unflatten_leading(y, lead),
            FFFOutput(leaf_idx=utils.unflatten_leading(leaf_idx, lead),
                      overflow_fraction=jnp.zeros((), jnp.float32)))


register_backend("train", "reference", _train_reference)
register_backend("train", "grouped", _train_grouped)
register_backend("infer", "reference", _infer_reference)
register_backend("infer", "grouped", _infer_grouped)
register_backend(
    "infer", "grouped_ep", _infer_grouped_ep,
    # auto/override eligibility: a model axis to exchange over and a leaf
    # count that divides across it (explicit specs still run — the backend
    # degrades gracefully unsharded)
    supports=lambda params, cfg: (
        cfg.depth > 0 and dist_act.model_shard_count() > 1
        and cfg.num_leaves % dist_act.model_shard_count() == 0))
register_backend(
    "infer", "pallas", _infer_pallas,
    # single-device kernels: ineligible under an SPMD mesh (sharded serving
    # wants the partitionable grouped dispatch, DESIGN.md §5)
    supports=lambda params, cfg: (_pallas_supported(params, cfg)
                                  and not dist_act.mesh_installed()))
register_backend(
    "infer", "pallas_decode", _infer_pallas_decode,
    # same single-device + kernel-eligibility constraints as "pallas", plus
    # a tree to descend (the megakernel's routing phase is the fusion's
    # whole point; depth-0 sites are a plain dense MLP)
    supports=lambda params, cfg: (cfg.depth > 0
                                  and _pallas_supported(params, cfg)
                                  and not dist_act.mesh_installed()))
