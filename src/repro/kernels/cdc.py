"""Pallas TPU kernels: windowed gear-hash CDC — boundary hashes AND cut
selection, fully device-resident.

GPU/CPU CDC rolls a hash byte-serially — useless on a vector unit. The TPU
adaptation exploits that a *windowed* gear hash at position i
depends only on the previous W=32 bytes:

    h_i = sum_{k=0}^{W-1} table[byte_{i-k}] << k        (uint32 wrap)

so every position is independent: the kernel computes W shifted vector adds
per tile — lane rotations and VPU adds, no sequential dependency. The
wrapper does the 256-entry gear-table gather in jnp (cheap, one take()) and
hands the kernel a uint32 stream; each tile carries a lane-aligned 128-value
halo on the left, of which the window reads W-1.

``cdc_hashes_pallas`` stops there (hashes only; host selects cuts).
``cdc_cuts_pallas`` fuses the whole CDC decision into ONE launch: each
grid step recomputes the tile's window hashes, derives the boundary-candidate
mask (hash & mask == 0) and then runs min/max-size cut selection as a
scan-style loop whose carry — the position after the last emitted cut — lives
in SMEM and persists across the sequential TPU grid (the ``lax.scan`` carry
idiom, block-at-a-time). Per candidate the loop does one vector min-reduce
over the tile, so cost is O(cuts_in_tile * tile); the selection is
bit-identical to the scalar oracle ``chunk_cdc_scalar`` (proof sketch in
docs/kernels.md). Streams are batched: grid = (stream, tile), the carry
resets at tile 0 of every stream and per-stream byte lengths ride in SMEM.
Each emitted cut's position is written to an SMEM output as well, so the
caller needs no compaction over the cut mask.

Memory, as the v5e compiler reports it: the hash kernel's (8, TL+128) u32
in / (8, TL) u32 out tiles take 136 KiB of scoped VMEM at TL=2048; the cut
kernel's (1, BLK+128) u32 in / (1, BLK) int8 out tiles take 129 KiB at
BLK=8192 (a one-row tile pads to eight sublanes). The cut kernel's SMEM
holds two int32 per tile, one cut slot per possible cut and three per
stream: about 130 KiB for a full save wave (WAVE_ROW_BYTES). A whole
1.56 GB leaf in one launch needed 1.45 MiB of SMEM, over the 1 MiB limit;
save waves bound it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import WINDOW

TILE_ROWS = 8          # sublane dim
TILE_LEN = 2048        # lane dim per row
HALO = 128             # lane-aligned left halo; the window reads WINDOW - 1


def _round_lanes(n: int) -> int:
    return -(-n // 128) * 128


def _window_hashes(t, width: int):
    """(R, HALO + width) halo'd table values -> (R, width) window hashes.

    Each of the W shifted windows is a lane rotation plus an aligned slice:
    on v5e, static slices at unaligned lane offsets compiled but returned
    wrong values for ~4% of positions.
    """
    total = t.shape[1]
    h = jnp.zeros((t.shape[0], width), dtype=jnp.uint32)
    for k in range(WINDOW):
        # rolled[:, j] == t[:, HALO - k + j]; k = 0 is the newest byte.
        rolled = pltpu.roll(t, shift=total - HALO + k, axis=1)
        h = h + (rolled[:, :width].astype(jnp.uint32) << jnp.uint32(k))
    return h


def _cdc_kernel(t_ref, out_ref):
    """t_ref: (R, HALO + TL) halo'd table values; out: (R, TL)."""
    out_ref[...] = _window_hashes(t_ref[...], out_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("interpret", "tile_len"))
def cdc_hashes_pallas(
    tvals: jnp.ndarray, *, interpret: bool = False, tile_len: int = TILE_LEN
) -> jnp.ndarray:
    """(n,) uint32 gear-table values -> (n,) uint32 window hashes.

    Bit-identical to ref.cdc_hashes (short windows at the stream head
    included, via zero halo).
    """
    assert tvals.ndim == 1
    n = tvals.shape[0]
    rows = TILE_ROWS
    tl = min(tile_len, _round_lanes(n))
    per_row = tl
    n_rows = -(-n // per_row)
    n_rows_pad = (-n_rows) % rows
    total_rows = n_rows + n_rows_pad

    flat = jnp.pad(tvals.astype(jnp.uint32), (0, total_rows * per_row - n))
    body = flat.reshape(total_rows, per_row)
    # Halo: last HALO values of the previous row (zero for row 0).
    halo = jnp.concatenate(
        [jnp.zeros((1, HALO), jnp.uint32), body[:-1, -HALO:]], axis=0
    )
    haloed = jnp.concatenate([halo, body], axis=1)       # (rows_t, HALO+TL)

    grid = (total_rows // rows,)
    out = pl.pallas_call(
        _cdc_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, HALO + per_row), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, per_row), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total_rows, per_row), jnp.uint32),
        interpret=interpret,
        name="cdc_hashes_pallas",
    )(haloed)
    return out.reshape(-1)[:n]


def cdc_boundaries_pallas(
    tvals: jnp.ndarray, mask: int, *, interpret: bool = False
) -> jnp.ndarray:
    return (cdc_hashes_pallas(tvals, interpret=interpret) & jnp.uint32(mask)) == 0


# --------------------------------------------------------------------------
# Fused hash + min/max-size cut selection (one launch per wave of streams).
# --------------------------------------------------------------------------

CUT_BLOCK_LEN = 8192   # positions per cut-selection grid step

# Fingerprint-row bytes per device save wave (``ops.wave_row_cap``). A fused
# wave's temporaries follow its row matrix, one max_size row per possible
# chunk. Compiled for one v5e chip at ChunkSpec.for_checkpoint(512 KiB), a
# full segment at this budget (127.5 MiB, 511 rows of 1 MiB) needs 1.87 GB
# of temporaries, so a wave plus two 5 GB pytrees on the chip stays under
# 12 GB of its 16 GiB.
WAVE_ROW_BYTES = 512 << 20
# Rows per wave at most, whatever the row size: the cut kernel keeps a cut
# slot per row and a stream id and tile index per 8 KiB tile in SMEM (1 MiB
# on v5e); 64 Ki rows bound them to a few hundred KiB.
WAVE_MAX_ROWS = 1 << 16


def max_cuts(n: int, min_size: int) -> int:
    """Static bound on the number of cuts in an n-byte stream: every cut
    advances the chunk start by at least min_size + 1 bytes."""
    return n // (min_size + 1) + 1


def _cdc_cut_kernel(
    len_ref, base_ref, tile_s_ref, tile_t_ref, th_ref,
    out_ref, cuts_ref, count_ref, carry_ref, *,
    mask: int, min_size: int, max_size: int, block_len: int,
):
    """One grid step = one (1, BLK) tile. Streams of arbitrary (different)
    lengths are concatenated tile-row-wise, so a wave wastes at most one
    block of padding per stream instead of rectangular S x Lmax padding.

    len_ref:    (S,) int32 per-stream byte lengths, SMEM.
    base_ref:   (S,) int32 offset of each stream's slots in cuts_ref, SMEM.
    tile_s_ref: (T_total,) int32 stream id of each tile row, SMEM.
    tile_t_ref: (T_total,) int32 tile index *within* its stream, SMEM.
    th_ref:     (1, HALO + BLK) uint32 halo'd gear-table values (the
                leading tile-row dim of the array is squeezed by the block).
    out_ref:    (1, BLK) int8 cut mask.
    cuts_ref:   (C_total,) int32 SMEM output: each stream's cut positions
                in order from its base (max_cuts slots per stream).
    count_ref:  (S,) int32 SMEM output: cuts per stream.
    carry_ref:  (2,) int32 SMEM scratch — persists across the sequential
                grid; holds the start of the current chunk (last cut + 1)
                and the stream's cuts so far.
    """
    g = pl.program_id(0)
    s = tile_s_ref[g]
    t = tile_t_ref[g]

    @pl.when(t == 0)
    def _reset():
        carry_ref[0] = 0
        carry_ref[1] = 0

    n = len_ref[s]
    base = base_ref[s]
    blk = block_len
    # Window hashes for this tile (same scheme as _cdc_kernel).
    h = _window_hashes(th_ref[...], blk)
    # Stream-local positions covered by this tile, and the candidate mask.
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) + t * blk
    cand = ((h & jnp.uint32(mask)) == 0) & (pos < n)
    blk_end = t * blk + blk - 1
    big = jnp.int32(2**30)

    # Scan carry = start of the current chunk. Invariant on tile entry: no
    # boundary candidate >= start + min_size exists before this tile (earlier
    # tiles drained themselves), so searching within the tile is exact.
    def _next_cut(sp):
        lo = sp + min_size
        hard = jnp.maximum(lo, sp + max_size - 1)
        cmin = jnp.min(jnp.where(cand & (pos >= lo), pos, big))
        return lo, jnp.minimum(cmin, hard)

    def _cond(c):
        sp, _, _ = c
        lo, cut = _next_cut(sp)
        return (lo < n) & (cut < n) & (cut <= blk_end)

    # The loop carries an int32 mask: Mosaic cannot yield a bool vector.
    def _body(c):
        sp, k, out = c
        _, cut = _next_cut(sp)
        cuts_ref[base + k] = cut
        return cut + 1, k + 1, jnp.where(pos == cut, jnp.int32(1), out)

    s_fin, k_fin, out = jax.lax.while_loop(
        _cond, _body, (carry_ref[0], carry_ref[1], jnp.zeros((1, blk), jnp.int32))
    )
    carry_ref[0] = s_fin
    carry_ref[1] = k_fin
    count_ref[s] = k_fin
    out_ref[...] = out.astype(out_ref.dtype)


def cdc_cuts_pallas(
    tvals_list: list[jnp.ndarray],
    *,
    mask: int,
    min_size: int,
    max_size: int,
    interpret: bool = False,
    block_len: int = CUT_BLOCK_LEN,
) -> list[tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Per-stream (n_i,) uint32 gear-table values -> per stream (cut mask
    (n_i,) bool, cut positions (max_cuts,) int32 with ``n_i`` past the
    last, cut count int32 scalar). Bit i of a mask is set iff the scalar
    oracle ``chunk_cdc_scalar`` ends a chunk at byte i.

    ONE launch for the whole wave: streams are tiled independently (so each
    keeps its own zero-prefix hash window and its own scan carry) and their
    tile rows concatenated; the grid walks all rows sequentially with the
    carry in SMEM, resetting at tile 0 of every stream. The kernel writes
    each cut's position as it emits it, so no compaction over the stream
    is needed afterwards.
    """
    assert tvals_list and all(t.ndim == 1 for t in tvals_list)
    assert min_size >= 1, "pass a normalized ChunkingSpec (min_size >= 1)"
    assert max_size >= min_size
    lens = [int(t.shape[0]) for t in tvals_list]
    assert all(n > 0 for n in lens), "drop empty streams before the kernel"
    blk = min(block_len, _round_lanes(max(lens)))
    slots = [max_cuts(n, min_size) for n in lens]
    bases = np.cumsum([0] + slots[:-1])
    tile_s: list[int] = []
    tile_t: list[int] = []
    bodies = []
    for s, (tv, n) in enumerate(zip(tvals_list, lens)):
        t_s = -(-n // blk)
        body = jnp.pad(tv.astype(jnp.uint32), (0, t_s * blk - n)).reshape(t_s, blk)
        bodies.append(body)
        tile_s.extend([s] * t_s)
        tile_t.extend(range(t_s))
    body = jnp.concatenate(bodies)                       # (T_total, blk)
    # Left halo per tile: last HALO values of the previous tile of the SAME
    # stream, zeros at tile 0 (short-prefix-window semantics at each
    # stream's head). tile_t == 0 marks stream starts.
    first = jnp.asarray(np.asarray(tile_t) == 0)[:, None]
    prev_tail = jnp.concatenate(
        [jnp.zeros((1, HALO), jnp.uint32), body[:-1, -HALO:]]
    )
    halo = jnp.where(first, jnp.uint32(0), prev_tail)
    haloed = jnp.concatenate([halo, body], axis=1)       # (T_total, HALO+blk)
    # A unit middle dim makes each tile's block (1, HALO+blk) equal the
    # array's last two dims, as the TPU block-shape rule requires.
    haloed = haloed[:, None, :]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out, cuts, counts = pl.pallas_call(
        functools.partial(
            _cdc_cut_kernel,
            mask=mask, min_size=min_size, max_size=max_size, block_len=blk,
        ),
        grid=(len(tile_s),),
        in_specs=[smem, smem, smem, smem,
                  pl.BlockSpec((None, 1, HALO + blk), lambda g: (g, 0, 0))],
        out_specs=[pl.BlockSpec((None, 1, blk), lambda g: (g, 0, 0)), smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((len(tile_s), 1, blk), jnp.int8),
            jax.ShapeDtypeStruct((sum(slots),), jnp.int32),
            jax.ShapeDtypeStruct((len(lens),), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        interpret=interpret,
        name="cdc_cuts_pallas",
    )(
        jnp.asarray(lens, jnp.int32),
        jnp.asarray(bases, jnp.int32),
        jnp.asarray(tile_s, jnp.int32),
        jnp.asarray(tile_t, jnp.int32),
        haloed,
    )
    res, row = [], 0
    for s, (n, m, b) in enumerate(zip(lens, slots, bases)):
        t_s = -(-n // blk)
        # Slots past the count were never written: fill them with n.
        pos = jnp.where(jnp.arange(m) < counts[s], cuts[b : b + m], n)
        res.append((out[row : row + t_s].reshape(-1)[:n] != 0, pos, counts[s]))
        row += t_s
    return res


def cdc_cut_masks_pallas(tvals_list: list[jnp.ndarray], **kw) -> list[jnp.ndarray]:
    """Per-stream cut masks of ``cdc_cuts_pallas`` (same arguments)."""
    return [m for m, _, _ in cdc_cuts_pallas(tvals_list, **kw)]
