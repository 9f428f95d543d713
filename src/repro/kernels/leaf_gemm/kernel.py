"""Pallas TPU kernel: ragged grouped GEMM over capacity-padded leaf groups.

The TPU-native replacement for the paper's CUDA "offset in the data load"
(DESIGN.md §3): tokens are sorted by routed leaf and scattered into padded
per-leaf buffers (E, C, D); the kernel is a tiled matmul whose weight block is
selected *by the grid index* (a static scalar-prefetch index map — the
offset-load equivalent), with compute skipped entirely for empty tiles via the
scalar-prefetched ``group_sizes`` (ragged early-out).

Two variants:
  * ``grouped_matmul``      — y[e] = act(x[e] @ w[e]) for MLP leaves
  * ``grouped_matmul_dual`` — y[e] = silu(x[e] @ wg[e]) * (x[e] @ wu[e]) for
    SwiGLU leaves (both ups fused: x tile loaded once, one pass over D)

Grid: (E, C/bc, H/bh, D/bk), k innermost for accumulation in a VMEM f32
scratch tile (bc, bh).  VMEM per step @ defaults (bc=128, bh=512, bk=512,
bf16): x 128 KiB + w 512 KiB + acc 256 KiB (+dual: 2x w/acc) — double-buffered
by the pipeline well inside budget; block sizes are 128-multiples for the MXU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _gmm_kernel(gs_ref, x_ref, w_ref, o_ref, acc_ref, *, act: str,
                block_c: int, out_dtype):
    e = pl.program_id(0)
    c = pl.program_id(1)
    k = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nonempty = gs_ref[e] > c * block_c

    @pl.when(nonempty)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[0] = _ACTS[act](acc_ref[...]).astype(out_dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   act: str = "none", block_c: int = 128, block_h: int = 512,
                   block_k: int = 512, interpret: bool = False,
                   out_dtype=None) -> jax.Array:
    """x (E, C, D) @ w (E, D, H) -> (E, C, H), skipping empty token tiles."""
    E, C, D = x.shape
    H = w.shape[2]
    out_dtype = out_dtype or x.dtype
    bc = min(block_c, C)
    bh = min(block_h, H)
    bk = min(block_k, D)
    while C % bc:
        bc -= 1
    while H % bh:
        bh -= 1
    while D % bk:
        bk -= 1
    grid = (E, C // bc, H // bh, D // bk)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, act=act, block_c=bc, out_dtype=out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, bk), lambda e, c, h, k, gs: (e, c, k)),
                pl.BlockSpec((1, bk, bh), lambda e, c, h, k, gs: (e, k, h)),
            ],
            out_specs=pl.BlockSpec((1, bc, bh), lambda e, c, h, k, gs: (e, c, h)),
            scratch_shapes=[pltpu.VMEM((bc, bh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, H), out_dtype),
        interpret=interpret,
        name="leaf_gemm",
    )(group_sizes, x, w)


def _gmm_dual_kernel(gs_ref, x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref,
                     *, block_c: int, out_dtype):
    e = pl.program_id(0)
    c = pl.program_id(1)
    k = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    nonempty = gs_ref[e] > c * block_c

    @pl.when(nonempty)
    def _compute():
        xt = x_ref[0]
        accg_ref[...] += jax.lax.dot_general(
            xt, wg_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        accu_ref[...] += jax.lax.dot_general(
            xt, wu_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[0] = (jax.nn.silu(accg_ref[...]) * accu_ref[...]).astype(out_dtype)


def grouped_matmul_dual(x: jax.Array, wg: jax.Array, wu: jax.Array,
                        group_sizes: jax.Array, *, block_c: int = 128,
                        block_h: int = 512, block_k: int = 512,
                        interpret: bool = False, out_dtype=None) -> jax.Array:
    """SwiGLU up: silu(x @ wg) * (x @ wu), grouped per leaf: -> (E, C, H)."""
    E, C, D = x.shape
    H = wg.shape[2]
    out_dtype = out_dtype or x.dtype
    bc = min(block_c, C)
    bh = min(block_h, H)
    bk = min(block_k, D)
    while C % bc:
        bc -= 1
    while H % bh:
        bh -= 1
    while D % bk:
        bk -= 1
    grid = (E, C // bc, H // bh, D // bk)
    return pl.pallas_call(
        functools.partial(_gmm_dual_kernel, block_c=bc, out_dtype=out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, bk), lambda e, c, h, k, gs: (e, c, k)),
                pl.BlockSpec((1, bk, bh), lambda e, c, h, k, gs: (e, k, h)),
                pl.BlockSpec((1, bk, bh), lambda e, c, h, k, gs: (e, k, h)),
            ],
            out_specs=pl.BlockSpec((1, bc, bh), lambda e, c, h, k, gs: (e, c, h)),
            scratch_shapes=[pltpu.VMEM((bc, bh), jnp.float32),
                            pltpu.VMEM((bc, bh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, H), out_dtype),
        interpret=interpret,
        name="leaf_gemm_dual",
    )(group_sizes, x, wg, wu)
