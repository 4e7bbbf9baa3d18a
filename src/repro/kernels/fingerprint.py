"""Pallas TPU kernel: 128-bit content fingerprints for on-device tensors.

The paper's future-work item is offloading fingerprint computation to an
accelerator ("GPU for parallel fingerprint computation"); here it runs on the
TPU VPU so checkpoint/KV chunks are fingerprinted *without* leaving HBM.

Grid layout: (chunk_tiles, word_tiles). The words axis is the reduction axis;
the commutative position-salted mix (see ref.py) makes grid-order-independent
accumulation legal. Each step loads a (TC, TW) uint32 tile into VMEM,
mixes it against the 4 lane constants, and accumulates into the (TC, 4)
output block, which stays resident in VMEM across the word_tiles loop
(output BlockSpec indexes only the chunk axis).

VMEM: with the default TC=256, TW=512 the input tile is 512 KiB; the v5e
compiler reports 1.00 MiB of scoped VMEM for the kernel (the tile double
buffered), well inside the chip's 16 MiB per core. Mosaic has no unsigned
reduction and no scatter, so the lane sums run as int32 (same wrap) and
land in their column by a lane select.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import A, B, C, LANES

# Tile sizes: TC chunks x TW words. Lane dim (128) aligned; TW multiple of
# 128 keeps loads in full VREG rows.
TILE_CHUNKS = 256
TILE_WORDS = 512


def _mix32_k(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _fingerprint_kernel(w_ref, out_ref, *, n_words_total: int, tile_words: int):
    """One grid step: accumulate lane sums for a (TC, TW) word tile."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.uint32)                     # (TC, TW)
    tc, tw = w.shape
    # Global word positions for this tile (1-based salt).
    pos = (
        jax.lax.broadcasted_iota(jnp.uint32, (tc, tw), 1)
        + jnp.uint32(1)
        + j.astype(jnp.uint32) * jnp.uint32(tile_words)
    )
    # Zero-padding words beyond n_words_total contribute mix(0*A + pos*B),
    # which is NOT zero — mask them out to match ref on exact shapes.
    valid = pos <= jnp.uint32(n_words_total)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    acc = out_ref[...]
    for lane in range(LANES):
        mixed = _mix32_k(w * jnp.uint32(int(A[lane])) + pos * jnp.uint32(int(B[lane])))
        mixed = jnp.where(valid, mixed, jnp.uint32(0))
        # Mosaic has no unsigned reduction: sum as int32, which wraps
        # bit-identically, and place the column with a lane select (no
        # scatter on TPU).
        part = jnp.sum(
            jax.lax.bitcast_convert_type(mixed, jnp.int32), axis=1, keepdims=True
        )
        part = jax.lax.bitcast_convert_type(part, jnp.uint32)
        acc = acc + jnp.where(lane_idx == lane, part, jnp.uint32(0))
    out_ref[...] = acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        fin = out_ref[...]
        tc_out = fin.shape[0]
        # Length salt per lane (scalar constants — no captured arrays).
        lane_idx = jax.lax.broadcasted_iota(jnp.int32, (tc_out, LANES), 1)
        salt = jnp.zeros((tc_out, LANES), jnp.uint32)
        for lane in range(LANES):
            salt = jnp.where(
                lane_idx == lane,
                jnp.uint32(n_words_total) * jnp.uint32(int(C[lane])),
                salt,
            )
        out_ref[...] = _mix32_k(fin + salt)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_chunks", "tile_words"))
def fingerprint_chunks_pallas(
    words: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_chunks: int = TILE_CHUNKS,
    tile_words: int = TILE_WORDS,
) -> jnp.ndarray:
    """(n_chunks, n_words) uint32 -> (n_chunks, 4) uint32 fingerprints.

    Pads both axes to tile multiples; padding is masked inside the kernel so
    results are bit-identical to ref.fingerprint_chunks on the true shape.
    """
    assert words.ndim == 2, words.shape
    n_chunks, n_words = words.shape
    tc = min(tile_chunks, max(8, n_chunks))
    tw = min(tile_words, max(128, n_words))
    pc = (-n_chunks) % tc
    pw = (-n_words) % tw
    wp = jnp.pad(words.astype(jnp.uint32), ((0, pc), (0, pw)))
    grid = (wp.shape[0] // tc, wp.shape[1] // tw)

    out = pl.pallas_call(
        functools.partial(
            _fingerprint_kernel, n_words_total=n_words, tile_words=tw
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((tc, tw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tc, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp.shape[0], LANES), jnp.uint32),
        interpret=interpret,
        # The op's name in compiled programs and device traces, whatever
        # jitted function holds the kernel (chipbench's fp_roofline.save
        # finds the kernel by it).
        name="fingerprint_chunks_pallas",
    )(wp)
    return out[:n_chunks]
