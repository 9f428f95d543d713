"""Attention: GQA with RoPE, flash-style chunked softmax, KV-cache decode.

Design notes (DESIGN.md §5):

* ``flash_attention`` is pure JAX: an online-softmax scan over a *static*
  list of (q-chunk, kv-chunk) pairs.  Causal masking is done by enumerating
  only the lower-triangle chunk pairs at trace time — no wasted upper-triangle
  FLOPs in the lowered HLO (this is what the roofline counts).  Sliding-window
  attention additionally drops chunk pairs outside the band, statically.
* GQA never materializes repeated KV heads: q is shaped (B, S, K, G, hd) and
  contractions carry the group axis.
* ``decode_attend`` attends one new token against a (possibly
  sequence-sharded) KV cache; softmax over a sharded S axis lowers to
  all-reduce(max)/all-reduce(sum) under SPMD — the long-context decode path.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import utils
from repro.nn import rope as rope_lib

Params = dict

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    sliding_window: int = 0          # 0 = full
    chunk: int = 1024                # flash chunk size
    param_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv_heads


def init(key: jax.Array, cfg: AttnConfig) -> Params:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    pd = cfg.param_dtype
    std = 1.0 / math.sqrt(D)
    p: Params = {
        "wq": utils.truncated_init(ks[0], (D, H, hd), std, pd),
        "wk": utils.truncated_init(ks[1], (D, K, hd), std, pd),
        "wv": utils.truncated_init(ks[2], (D, K, hd), std, pd),
        "wo": utils.truncated_init(ks[3], (H, hd, D), 1.0 / math.sqrt(H * hd), pd),
    }
    if cfg.bias:
        p["bq"] = jnp.zeros((H, hd), pd)
        p["bk"] = jnp.zeros((K, hd), pd)
        p["bv"] = jnp.zeros((K, hd), pd)
        p["bo"] = jnp.zeros((D,), pd)
    return p


def qkv(params: Params, cfg: AttnConfig, x: jax.Array,
        positions: Optional[jax.Array]) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, D) -> q (B, S, H, hd), k/v (B, S, K, hd), RoPE applied."""
    ad = cfg.accum_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"], preferred_element_type=ad)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"], preferred_element_type=ad)
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"], preferred_element_type=ad)
    if cfg.bias:
        q = q + params["bq"].astype(ad)
        k = k + params["bk"].astype(ad)
        v = v + params["bv"].astype(ad)
    if cfg.use_rope and positions is not None:
        q = rope_lib.apply_rope(q, positions, cfg.rope_theta)
        k = rope_lib.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_proj(params: Params, cfg: AttnConfig, o: jax.Array) -> jax.Array:
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"],
                   preferred_element_type=cfg.accum_dtype)
    if cfg.bias:
        y = y + params["bo"].astype(cfg.accum_dtype)
    return y


# ---------------------------------------------------------------------------
# flash attention over static chunk pairs
# ---------------------------------------------------------------------------

def _chunk_pairs(n_q: int, n_k: int, causal: bool, window_chunks: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Static (qi, kj, needs_mask) schedule.

    causal: only kj <= qi (equal-length q/k assumed); diagonal chunk masked.
    window_chunks w > 0: additionally require qi - kj <= w (band)."""
    qs, ks, masked = [], [], []
    for qi in range(n_q):
        for kj in range(n_k):
            if causal and kj > qi:
                continue
            if window_chunks > 0 and qi - kj > window_chunks:
                continue
            qs.append(qi)
            ks.append(kj)
            masked.append(causal and kj == qi or window_chunks > 0
                          and qi - kj == window_chunks)
    return (jnp.asarray(qs, jnp.int32), jnp.asarray(ks, jnp.int32),
            jnp.asarray(masked, jnp.bool_))


def _expand_kv(kv: jax.Array, groups: int) -> jax.Array:
    """(B, S, K, hd) -> (B, S, K*G, hd): materialize KV per q-head.

    Sharding rationale (DESIGN.md §5): GQA KV-head counts (4-16) do not divide
    the 16-way model axis, and the (K, G) head-grouping reshape forces the
    SPMD partitioner into involuntary rematerialization.  Expanding KV keeps
    every attention tensor sharded on the full H axis; the duplicated KV bytes
    are per-layer transients and are the cheaper trade (measured: §Perf)."""
    if groups == 1:
        return kv
    return jnp.repeat(kv, groups, axis=2)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, chunk: int = 1024,
                    sliding_window: int = 0) -> jax.Array:
    """Online-softmax attention.

    q (B, S, H, hd); k, v (B, S, K, hd) with H = K * G.  Returns (B, S, H, hd).
    The scan carries full-size (m, l, acc) accumulators and visits only the
    statically scheduled chunk pairs, updating the q-chunk rows in place.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    chunk = min(chunk, S)
    if S % chunk:
        chunk = math.gcd(S, chunk) or S
    n_chunks = S // chunk
    scale = 1.0 / math.sqrt(hd)
    window_chunks = 0
    if sliding_window > 0:
        window_chunks = max(1, utils.cdiv(sliding_window, chunk))
    qi_l, kj_l, mk_l = _chunk_pairs(n_chunks, n_chunks, causal, window_chunks)

    qf = q.astype(jnp.float32)
    kf = _expand_kv(k, G).astype(jnp.float32)
    vf = _expand_kv(v, G).astype(jnp.float32)

    m0 = jnp.full((B, S, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, hd), jnp.float32)

    col = jnp.arange(chunk)

    def body(carry, step):
        m, l, acc = carry
        qi, kj, needs_mask = step
        qs = qi * chunk
        ks_ = kj * chunk
        qc = jax.lax.dynamic_slice_in_dim(qf, qs, chunk, axis=1)      # (B,c,H,hd)
        kc = jax.lax.dynamic_slice_in_dim(kf, ks_, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vf, ks_, chunk, axis=1)
        s = jnp.einsum("bqhd,bphd->bhqp", qc, kc) * scale             # (B,H,c,c)
        if causal or sliding_window > 0:
            qpos = qs + col[:, None]
            kpos = ks_ + col[None, :]
            ok = jnp.ones((chunk, chunk), bool)
            if causal:
                ok &= kpos <= qpos
            if sliding_window > 0:
                ok &= qpos - kpos < sliding_window
            s = jnp.where(needs_mask, jnp.where(ok, s, NEG_INF), s)
        m_chunk = jax.lax.dynamic_slice_in_dim(m, qs, chunk, axis=1)  # (B,c,H)
        l_chunk = jax.lax.dynamic_slice_in_dim(l, qs, chunk, axis=1)
        a_chunk = jax.lax.dynamic_slice_in_dim(acc, qs, chunk, axis=1)
        m_cur = m_chunk.transpose(0, 2, 1)                            # (B,H,c)
        m_new = jnp.maximum(m_cur, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_cur - m_new)
        l_new = l_chunk.transpose(0, 2, 1) * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqp,bphd->bhqd", p, vc)
        a_new = a_chunk.transpose(0, 2, 1, 3) * corr[..., None] + pv
        m = jax.lax.dynamic_update_slice_in_dim(
            m, m_new.transpose(0, 2, 1), qs, axis=1)
        l = jax.lax.dynamic_update_slice_in_dim(
            l, l_new.transpose(0, 2, 1), qs, axis=1)
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, a_new.transpose(0, 2, 1, 3), qs, axis=1)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (qi_l, kj_l, mk_l))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, bias_mask: Optional[jax.Array] = None
                   ) -> jax.Array:
    """Plain materialized-scores attention — oracle and short-sequence path."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    Sk = k.shape[1]
    kf = _expand_kv(k, G).astype(jnp.float32)
    vf = _expand_kv(v, G).astype(jnp.float32)
    s = jnp.einsum("bqhd,bphd->bhqp", q.astype(jnp.float32), kf)
    s = s / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        s = jnp.where(mask, s, NEG_INF)
    if bias_mask is not None:
        s = jnp.where(bias_mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqp,bphd->bqhd", p, vf)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Paged KV cache: a fixed page pool indexed through per-row page tables.

    ``k``/``v`` are pools of ``num_pages`` fixed-size pages shared by all
    rows; row b's logical positions ``p`` live at
    ``pool[table[b, p // page_size], p % page_size]``.  Unmapped table
    entries hold the sentinel ``num_pages`` — writes through them are
    dropped and reads clamp to an arbitrary page whose values are masked
    out by the ``length`` check.  Two rows may map the same page (shared
    prompt prefix, DESIGN.md §11); the host-side allocator guarantees a
    shared page is never written.

    The contiguous cache of earlier revisions is the degenerate case
    ``page_size == max_len`` with an identity table (row b owns page b) —
    ``init_cache``'s default — and is bit-for-bit unchanged.
    """
    k: jax.Array          # (num_pages, page_size, K, hd) page pool
    v: jax.Array          # (num_pages, page_size, K, hd)
    table: jax.Array      # (B, pages_per_row) int32 page ids
    length: jax.Array     # (B,) int32 filled positions


def init_cache(batch: int, max_len: int, cfg: AttnConfig, dtype=None, *,
               page_size: int = 0, num_pages: int = 0,
               prealloc: bool = True) -> KVCache:
    """``page_size <= 0`` selects the degenerate contiguous layout (one
    ``max_len``-sized page per row).  ``prealloc`` maps row b to pages
    ``[b*ppr, (b+1)*ppr)`` identity-style — standalone callers (generate,
    tests) need a ready-to-write table; the serving engine passes
    ``prealloc=False`` and installs allocator-managed tables per admission."""
    dtype = dtype or cfg.param_dtype
    if page_size <= 0:
        page_size = max_len
    ppr = utils.cdiv(max_len, page_size)                 # pages per row
    if num_pages <= 0:
        num_pages = batch * ppr
    if prealloc:
        if num_pages < batch * ppr:
            raise ValueError(f"prealloc needs {batch * ppr} pages, "
                             f"pool has {num_pages}")
        table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * ppr
                 + jnp.arange(ppr, dtype=jnp.int32)[None, :])
    else:
        table = jnp.full((batch, ppr), num_pages, jnp.int32)
    shp = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shp, dtype), jnp.zeros(shp, dtype), table,
                   jnp.zeros((batch,), jnp.int32))


def gather_cache_kv(cache: KVCache) -> tuple[jax.Array, jax.Array]:
    """Materialize per-row K/V views (B, ppr*page, K, hd) from the pool.

    Sentinel table entries clamp to the last page; the garbage they gather
    is finite (pools are zero-initialized) and always masked by the
    caller's ``pos < length`` check."""
    num_pages, page = cache.k.shape[:2]
    tbl = jnp.minimum(cache.table, num_pages - 1)
    B, ppr = tbl.shape
    shp = (B, ppr * page) + cache.k.shape[2:]
    return cache.k[tbl].reshape(shp), cache.v[tbl].reshape(shp)


def prefill_into_cache(cache: KVCache, k: jax.Array, v: jax.Array) -> KVCache:
    """Write a full prefix (B, S, K, hd) at position 0."""
    S = k.shape[1]
    zeroed = cache._replace(length=jnp.zeros_like(cache.length))
    return chunk_into_cache(zeroed, k, v, jnp.full_like(cache.length, S))


def append_to_cache(cache: KVCache, k1: jax.Array, v1: jax.Array,
                    write_mask: Optional[jax.Array] = None) -> KVCache:
    """Append one token (B, 1, K, hd) at each sequence's current length.

    ``write_mask`` (B,) bool, optional: rows where it is False neither write
    K/V nor advance ``length`` (their write index is pushed out of bounds and
    dropped).  The continuous-batching engine uses this to decode a full
    ``(num_slots, 1)`` batch while some slots are mid-chunked-prefill — those
    rows' caches must not be perturbed by the dummy decode token.

    This is the C = 1 case of ``chunk_into_cache`` (write_mask == that
    row's valid_len), delegated so the masked-scatter/length invariant
    lives in one place."""
    B = k1.shape[0]
    valid = (jnp.ones((B,), jnp.int32) if write_mask is None
             else write_mask.astype(jnp.int32))
    return chunk_into_cache(cache, k1, v1, valid)


def chunk_into_cache(cache: KVCache, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array) -> KVCache:
    """Write a chunk (B, C, K, hd) at each row's current length (chunked
    prefill, DESIGN.md §9).

    Row b's first ``valid_len[b]`` positions land at logical positions
    ``length[b] .. length[b] + valid_len[b] - 1``, scattered through the
    row's page table into the pool; the rest of the chunk is padding whose
    page ids are pushed to the ``num_pages`` sentinel and dropped, so rows
    with no prefill work this step (``valid_len == 0``) are untouched.
    Positions past the row's mapped pages are likewise dropped (second
    line of defense — unmapped table entries already hold the sentinel).
    ``length`` advances by ``valid_len``."""
    B, C = k.shape[:2]
    num_pages, page = cache.k.shape[:2]
    ppr = cache.table.shape[1]
    col = jnp.arange(C)[None, :]                                  # (1, C)
    pos = cache.length[:, None] + col                             # (B, C)
    pg = pos // page                                              # (B, C)
    pid = jnp.take_along_axis(cache.table, jnp.minimum(pg, ppr - 1), axis=1)
    ok = (col < valid_len[:, None]) & (pg < ppr)
    pid = jnp.where(ok, pid, num_pages)                # pad/inactive: drop
    off = pos % page
    new_k = cache.k.at[pid, off].set(k.astype(cache.k.dtype), mode="drop")
    new_v = cache.v.at[pid, off].set(v.astype(cache.v.dtype), mode="drop")
    return cache._replace(
        k=new_k, v=new_v,
        length=cache.length + valid_len.astype(cache.length.dtype))


def decode_attend(q1: jax.Array, cache: KVCache, *, sliding_window: int = 0
                  ) -> jax.Array:
    """One-token attention against the cache.

    q1 (B, 1, H, hd) -> (B, 1, H, hd).  Valid-length masking uses the cache's
    per-sequence ``length``.  With a sequence-sharded cache the max/sum over S
    lower to all-reduces under SPMD (long-context decode path).

    The GQA contraction stays on the K axis here (no KV expansion): decode is
    memory-bound on the cache read, and the score tensor is tiny."""
    B, _, H, hd = q1.shape
    K = cache.k.shape[2]
    G = H // K
    kc, vc = gather_cache_kv(cache)                    # (B, ppr*page, K, hd)
    S = kc.shape[1]
    qg = q1.reshape(B, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bpkd->bkgp", qg, kc.astype(jnp.float32))
    s = s / math.sqrt(hd)
    pos = jnp.arange(S)[None, :]                                  # (1, S)
    valid = pos < cache.length[:, None]
    if sliding_window > 0:
        valid &= pos >= (cache.length[:, None] - sliding_window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgp,bpkd->bkgd", p, vc.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q1.dtype)


def chunk_attend(q: jax.Array, cache: KVCache, start: jax.Array, *,
                 sliding_window: int = 0) -> jax.Array:
    """Chunk attention against the cache with per-row positions.

    q (B, C, H, hd) holds the chunk's queries; row b's query i sits at
    absolute position ``start[b] + i`` and attends to cache positions
    ``<= start[b] + i`` — the row's history (previous chunks, already in the
    cache) plus the chunk's own causal prefix (this chunk's K/V must already
    be written at ``start[b]..``; see ``chunk_into_cache``).  Like
    ``decode_attend``, the GQA contraction stays on the K axis and the mask
    is per-row, so rows of one batch may sit at different offsets."""
    B, C, H, hd = q.shape
    K = cache.k.shape[2]
    G = H // K
    kc, vc = gather_cache_kv(cache)                    # (B, ppr*page, K, hd)
    S = kc.shape[1]
    qg = q.reshape(B, C, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bckgd,bpkd->bkgcp", qg, kc.astype(jnp.float32))
    s = s / math.sqrt(hd)
    qpos = start[:, None] + jnp.arange(C)[None, :]                # (B, C)
    kpos = jnp.arange(S)[None, None, :]                           # (1, 1, S)
    valid = kpos <= qpos[:, :, None]                              # (B, C, S)
    if sliding_window > 0:
        valid &= kpos > qpos[:, :, None] - sliding_window
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcp,bpkd->bckgd", p, vc.astype(jnp.float32))
    return o.reshape(B, C, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# block-level entry points
# ---------------------------------------------------------------------------

def _scoped(fn):
    """Trace ``fn`` under the ``attention`` name scope: its ops carry the
    name in their HLO metadata (the device trace's per-scope time)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("attention"):
            return fn(*args, **kwargs)
    return scoped


@_scoped
def forward(params: Params, cfg: AttnConfig, x: jax.Array,
            positions: Optional[jax.Array] = None,
            use_flash_above: int = 2048) -> jax.Array:
    """Self-attention over a full sequence (train / prefill without cache)."""
    B, S, _ = x.shape
    if positions is None and cfg.use_rope:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k, v = qkv(params, cfg, x, positions)
    if S > use_flash_above:
        o = flash_attention(q, k, v, causal=cfg.causal, chunk=cfg.chunk,
                            sliding_window=cfg.sliding_window)
    else:
        band = None
        if cfg.sliding_window > 0 and S > cfg.sliding_window:
            band = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :]) \
                < cfg.sliding_window
        o = full_attention(q, k, v, causal=cfg.causal, bias_mask=band)
    return out_proj(params, cfg, o)


@_scoped
def forward_prefill(params: Params, cfg: AttnConfig, x: jax.Array,
                    cache: KVCache, use_flash_above: int = 2048
                    ) -> tuple[jax.Array, KVCache]:
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k, v = qkv(params, cfg, x, positions if cfg.use_rope else None)
    cache = prefill_into_cache(cache, k, v)
    if S > use_flash_above:
        o = flash_attention(q, k, v, causal=cfg.causal, chunk=cfg.chunk,
                            sliding_window=cfg.sliding_window)
    else:
        o = full_attention(q, k, v, causal=cfg.causal)
    return out_proj(params, cfg, o), cache


@_scoped
def forward_decode(params: Params, cfg: AttnConfig, x1: jax.Array,
                   cache: KVCache, write_mask: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, KVCache]:
    """One decode step: x1 (B, 1, D).  Rows where ``write_mask`` (B,) is
    False leave the cache untouched (see ``append_to_cache``) — their
    attention output is still computed but the caller ignores it."""
    positions = cache.length[:, None] if cfg.use_rope else None   # (B, 1)
    q, k, v = qkv(params, cfg, x1, positions)
    cache = append_to_cache(cache, k, v, write_mask)
    o = decode_attend(q, cache, sliding_window=cfg.sliding_window)
    return out_proj(params, cfg, o), cache


@_scoped
def forward_chunk(params: Params, cfg: AttnConfig, x: jax.Array,
                  cache: KVCache, valid_len: jax.Array
                  ) -> tuple[jax.Array, KVCache]:
    """Chunked prefill: x (B, C, D) continues each row's sequence at its
    current cache length (DESIGN.md §9).

    Row b's first ``valid_len[b]`` chunk positions are real tokens — written
    into the cache and attended causally against the row's full history —
    while pad positions (and rows with ``valid_len == 0``) write nothing and
    produce garbage outputs the caller ignores.  RoPE positions are absolute:
    ``cache.length[b] + i``."""
    B, C, _ = x.shape
    start = cache.length                                          # (B,)
    positions = start[:, None] + jnp.arange(C)[None, :]           # (B, C)
    q, k, v = qkv(params, cfg, x, positions if cfg.use_rope else None)
    cache = chunk_into_cache(cache, k, v, valid_len)
    o = chunk_attend(q, cache, start, sliding_window=cfg.sliding_window)
    return out_proj(params, cfg, o), cache


@_scoped
def forward_cross(params: Params, cfg: AttnConfig, x: jax.Array,
                  enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Cross-attention: queries from x (B, S, D), precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"],
                   preferred_element_type=cfg.accum_dtype)
    if cfg.bias:
        q = q + params["bq"].astype(cfg.accum_dtype)
    o = full_attention(q, enc_k, enc_v, causal=False)
    return out_proj(params, cfg, o)


def cross_kv(params: Params, cfg: AttnConfig, enc_out: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"],
                   preferred_element_type=cfg.accum_dtype)
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"],
                   preferred_element_type=cfg.accum_dtype)
    if cfg.bias:
        k = k + params["bk"].astype(cfg.accum_dtype)
        v = v + params["bv"].astype(cfg.accum_dtype)
    return k, v
