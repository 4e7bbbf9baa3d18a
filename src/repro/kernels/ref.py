"""Pure-jnp oracles for the dedup hot-spot kernels.

These define the *semantics*; the Pallas kernels in fingerprint.py / cdc.py
must match them bit-exactly (uint32 wrap-around arithmetic everywhere).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# 128-bit tensor fingerprint (4 x uint32 lanes).
#
# Commutative position-salted multilinear mix: for lane l,
#   h_l = finalize( sum_i mix( w_i * A_l + (pos_i + 1) * B_l ) + n * C_l )
# The sum is associative/commutative => tile-parallel with any grid order.
# mix = xorshift-multiply avalanche (murmur3-style finalizer).
# ---------------------------------------------------------------------------

LANES = 4
# Odd multipliers per lane (distinct golden-ratio-ish constants).
A = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F], dtype=np.uint32)
B = np.array([0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09], dtype=np.uint32)
C = np.array([0x94D049BB, 0xBF58476D, 0x2545F491, 0x9E3779B9], dtype=np.uint32)


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 fmix32 avalanche on uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def fingerprint_chunks(words: jnp.ndarray) -> jnp.ndarray:
    """words: (n_chunks, chunk_words) uint32 -> (n_chunks, 4) uint32.

    Each row is fingerprinted independently; padding words MUST already be
    zeroed and the true length salted in by the caller (ops.py does both).
    """
    assert words.ndim == 2, words.shape
    w = words.astype(jnp.uint32)
    n_chunks, n_words = w.shape
    pos = (jnp.arange(n_words, dtype=jnp.uint32) + jnp.uint32(1))[None, :, None]
    wl = w[:, :, None]                                   # (c, w, 1)
    a = jnp.asarray(A)[None, None, :]                    # (1, 1, 4)
    b = jnp.asarray(B)[None, None, :]
    mixed = _mix32(wl * a + pos * b)                     # (c, w, 4)
    acc = jnp.sum(mixed.astype(jnp.uint32), axis=1, dtype=jnp.uint32)
    acc = acc + jnp.uint32(n_words) * jnp.asarray(C)[None, :]
    return _mix32(acc)


# ---------------------------------------------------------------------------
# Windowed gear-hash CDC boundaries.
#
#   h_i = sum_{k=0}^{W-1} table[byte_{i-k}] << k      (uint32 wrap)
#   boundary_i = (h_i & mask) == 0
#
# Matches repro.core.chunking.window_hash_at (the host path) for i >= W-1.
# ---------------------------------------------------------------------------

WINDOW = 32


def cdc_hashes(tvals: jnp.ndarray) -> jnp.ndarray:
    """tvals: (n,) uint32 gear-table values per byte -> (n,) window hashes.

    Positions i < WINDOW-1 use the short prefix window (same as host path).
    """
    t = tvals.astype(jnp.uint32)
    n = t.shape[0]
    h = jnp.zeros((n,), dtype=jnp.uint32)
    for k in range(WINDOW):
        shifted = jnp.zeros_like(t).at[k:].set(t[: n - k] if k else t)
        h = h + (shifted << jnp.uint32(k))
    return h


def cdc_boundaries(tvals: jnp.ndarray, mask: int) -> jnp.ndarray:
    return (cdc_hashes(tvals) & jnp.uint32(mask)) == 0


# ---------------------------------------------------------------------------
# Min/max-size cut selection over the candidate mask — the jnp oracle the
# fused Pallas kernel (cdc.cdc_cuts_pallas) must match bit-exactly, which
# in turn matches the scalar chunk_cdc_scalar loop:
#
#   start = 0
#   repeat: lo = start + min_size; stop if lo >= n
#           hard = max(lo, start + max_size - 1)
#           cut  = first candidate >= lo if <= hard else hard
#           stop if cut >= n; emit cut; start = cut + 1
# ---------------------------------------------------------------------------


def cdc_cut_mask(
    cand: jnp.ndarray, n: int, min_size: int, max_size: int
) -> jnp.ndarray:
    """(m,) bool candidate mask (positions < n beyond which it is ignored)
    -> (m,) bool cut mask, as a ``lax.while_loop`` with carry = chunk start.
    """
    assert cand.ndim == 1
    m = cand.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.bool_)
    pos = jnp.arange(m, dtype=jnp.int32)
    cand = cand & (pos < n)
    big = jnp.int32(2**30)

    def _next_cut(sp):
        lo = sp + min_size
        hard = jnp.maximum(lo, sp + max_size - 1)
        cmin = jnp.min(jnp.where(cand & (pos >= lo), pos, big))
        return lo, jnp.minimum(cmin, hard)

    def _cond(c):
        sp, _ = c
        lo, cut = _next_cut(sp)
        return (lo < n) & (cut < n)

    def _body(c):
        sp, out = c
        _, cut = _next_cut(sp)
        return cut + 1, out | (pos == cut)

    _, out = jax.lax.while_loop(
        _cond, _body, (jnp.int32(0), jnp.zeros((m,), jnp.bool_))
    )
    return out
