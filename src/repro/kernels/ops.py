"""Public jit'd wrappers over the dedup kernels.

On TPU these call the Pallas kernels compiled; everywhere else they run the
kernels in interpret mode (bit-identical) or fall back to the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunking import GEAR_TABLE
from repro.core.fingerprint import Fingerprint, device_fp
from repro.kernels import ref
from repro.kernels.cdc import (
    CUT_BLOCK_LEN,
    WAVE_MAX_ROWS,
    WAVE_ROW_BYTES,
    cdc_cut_masks_pallas,
    cdc_cuts_pallas,
    cdc_hashes_pallas,
    max_cuts,
)
from repro.kernels.fingerprint import fingerprint_chunks_pallas
from repro.tracing import span


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Semantic launch counters: one increment per wrapper call = one kernel
# launch on the TPU route (the jnp fallbacks count identically so the
# one-launch-per-wave contract is assertable everywhere). Python-side on
# purpose: increments happen per *call*, not per trace.
launch_counts = {"cdc": 0, "fingerprint": 0}


def _count_launch(kind: str) -> None:
    launch_counts[kind] += 1


def launch_snapshot() -> dict[str, int]:
    """Copy of the cumulative launch counters (for delta accounting)."""
    return dict(launch_counts)


def fingerprint_chunks(words: jnp.ndarray, *, use_pallas: bool | None = None) -> jnp.ndarray:
    """(n_chunks, n_words) uint32 -> (n_chunks, 4) uint32."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    _count_launch("fingerprint")
    if use_pallas:
        return fingerprint_chunks_pallas(words)
    return ref.fingerprint_chunks(words)


def _word_rows(flat_u32, chunk_words: int):
    """(n,) uint32 -> (ceil(n / chunk_words), chunk_words), zero padded."""
    pad = (-flat_u32.shape[0]) % chunk_words
    return jnp.pad(flat_u32, (0, pad)).reshape(-1, chunk_words)


@functools.partial(jax.jit, static_argnames=("chunk_words", "use_pallas"))
def _fingerprint_tensor_impl(flat_u32, *, chunk_words: int, use_pallas: bool):
    w = _word_rows(flat_u32, chunk_words)
    if use_pallas:
        return fingerprint_chunks_pallas(w)
    return ref.fingerprint_chunks(w)


def _pack_words(u8: jnp.ndarray) -> jnp.ndarray:
    """(4m,) uint8 -> (m,) little-endian uint32, from four strided byte
    planes: a (m, 4) uint8 view would pad its minor dim to 128 lanes on the
    TPU."""
    return functools.reduce(
        jnp.bitwise_or,
        [u8[..., b::4].astype(jnp.uint32) << (8 * b) for b in range(4)],
    )


@functools.lru_cache(maxsize=None)
def _interleave_matrix(k: int) -> np.ndarray:
    """One-hot (k*128, k*128) matrix sending lane l of byte plane b to lane
    k*l + b."""
    perm = np.zeros((k * 128, k * 128), np.float32)
    for b in range(k):
        perm[b * 128 + np.arange(128), k * np.arange(128) + b] = 1
    return perm


def _interleave_bytes(planes: list[jnp.ndarray]) -> jnp.ndarray:
    """k (m,) uint8 planes -> (k*m,) uint8 stream, out[k*i + b] =
    planes[b][i].

    Runs as a one-hot matmul on the MXU: bytes are exact in bf16 and each
    output has one nonzero term. The direct route (bitcast to (m, k) uint8,
    then flatten) pads the minor dim to 128 lanes on the TPU, 64x the
    stream for bf16.
    """
    k, m = len(planes), planes[0].shape[0]
    pad = (-m) % 128
    p = jnp.concatenate(
        [jnp.pad(q, (0, pad)).reshape(-1, 128) for q in planes], axis=1
    ).astype(jnp.bfloat16)
    out = jnp.dot(
        p,
        jnp.asarray(_interleave_matrix(k), jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    flat = jax.lax.optimization_barrier(out.astype(jnp.int32).reshape(-1))
    return flat[: k * m].astype(jnp.uint8)


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def tensor_to_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Bitcast any tensor to its flat little-endian uint32 stream.

    4-byte dtypes bitcast 1:1; wider dtypes (f64/i64) split into itemsize//4
    words each in memory order; sub-word dtypes (u8/bf16/f16) widen by
    little-endian byte packing, zero-padded to a word multiple. Matches
    ``np.frombuffer(arr.tobytes() + pad, "<u4")`` on the same values.
    """
    flat = x.reshape(-1)
    nbytes = flat.dtype.itemsize
    if nbytes % 4 == 0:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    as_u8 = tensor_to_u8(flat)
    return _pack_words(jnp.pad(as_u8, (0, (-as_u8.shape[0]) % 4)))


@jax.jit
def tensor_to_u8(x: jnp.ndarray) -> jnp.ndarray:
    """Bitcast any tensor to its flat little-endian byte stream, staying on
    device."""
    flat = x.reshape(-1)
    if flat.dtype == jnp.bool_:
        flat = flat.astype(jnp.uint8)
    k = flat.dtype.itemsize
    u = jax.lax.bitcast_convert_type(flat, _UINT[k])
    if k == 1:
        return u
    return _interleave_bytes([(u >> (8 * b)).astype(jnp.uint8) for b in range(k)])


def fingerprint_tensor_chunks(
    x: jnp.ndarray, chunk_bytes: int = 512 * 1024, *, use_pallas: bool | None = None
) -> jnp.ndarray:
    """Fingerprint a tensor in chunk_bytes-sized pieces on device.

    Returns (n_chunks, 4) uint32. Used by dedup checkpointing to name chunks
    without host round-trips.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    chunk_words = max(128, chunk_bytes // 4)
    flat = tensor_to_u32(x)
    _count_launch("fingerprint")
    return _fingerprint_tensor_impl(flat, chunk_words=chunk_words, use_pallas=use_pallas)


def device_fps_to_host(fps_u32: jnp.ndarray) -> list[Fingerprint]:
    """Convert kernel output rows into namespaced Fingerprint objects."""
    rows = np.asarray(jax.device_get(fps_u32))
    return [device_fp([int(w) for w in row]) for row in rows]


# Plain numpy constant: safe to close over from inside jit traces (a cached
# jnp array would leak a tracer when first materialized inside a trace).
_GEAR = np.array(GEAR_TABLE, dtype=np.uint32)


def _gear_jnp() -> np.ndarray:
    return _GEAR


def flash_attention(
    q: jnp.ndarray,             # (B, Sq, H, hd)
    k: jnp.ndarray,             # (B, Skv, K, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    use_pallas: bool | None = None,
) -> jnp.ndarray:
    """Fused attention: Pallas kernel on TPU (K/V-resident blocking, see
    repro.kernels.flash_attn), JAX chunked-attention fallback elsewhere or
    when K/V exceed the VMEM-resident budget. Returns (B, Sq, H, hd)."""
    import math

    from repro.kernels.flash_attn import flash_attention_pallas
    from repro.models.layers import chunked_attention

    if use_pallas is None:
        use_pallas = _on_tpu() and k.shape[1] <= 24 * 1024
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal, window=window)
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, sq, kh, h // kh, hd)
    out = chunked_attention(
        qg, k, v, causal=causal, window=window, mask_offset=0,
        q_chunk=2048, kv_chunk=1024, scale=1.0 / math.sqrt(hd),
    )
    return out.reshape(b, sq, h, hd)


def cdc_window_hashes(
    data_u8: jnp.ndarray, *, use_pallas: bool | None = None
) -> jnp.ndarray:
    """(n,) uint8 byte stream -> (n,) uint32 window hashes, bit-identical to
    the host ``repro.core.chunking.window_hashes`` (and its scalar oracle).
    Device route for the vectorized chunker: Pallas on TPU, jnp elsewhere."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    tvals = jnp.take(_gear_jnp(), data_u8.astype(jnp.int32))
    _count_launch("cdc")
    if use_pallas:
        return cdc_hashes_pallas(tvals)
    return ref.cdc_hashes(tvals)


def cdc_boundaries(
    data_u8: jnp.ndarray, mask: int, *, use_pallas: bool | None = None
) -> jnp.ndarray:
    """(n,) uint8 byte stream -> (n,) bool boundary mask."""
    h = cdc_window_hashes(data_u8, use_pallas=use_pallas)
    return (h & jnp.uint32(mask)) == 0


# ---------------------------------------------------------------------------
# Device-resident CDC cut selection fused with fingerprinting: the whole
# chunk-naming stage (window hashes -> min/max-size cut selection -> per-chunk
# fingerprints) runs without leaving the device, in exactly ONE CDC launch and
# ONE fingerprint launch per wave of streams.
# ---------------------------------------------------------------------------


def fp_row_words(max_size: int) -> tuple[int, int]:
    """Fused-fingerprint row geometry for chunks up to ``max_size`` bytes.

    Returns (payload_words, padded_width). A chunk's row is its bytes packed
    little-endian into ``payload_words`` uint32 (zero-padded), the chunk's
    byte length in the word right after the payload (so zero-extended chunks
    of different lengths can never collide), then zero padding to a
    lane-aligned ``padded_width``. Fingerprint of a chunk == ``ref.
    fingerprint_chunks`` of its row — one fixed, kernel-friendly contract
    shared by the device route and the host oracle in tests.
    """
    payload = -(-max_size // 4)
    width = payload + 1
    width = width + (-width) % 128
    return payload, max(128, width)


def _chunk_table(cutpos, n_cuts, *, n: int):
    """Per-stream chunk table from its cut positions.

    cutpos: (m_cut,) i32, the first ``n_cuts`` valid. Returns (starts (M,)
    i32, lens (M,) i32, n_chunks i32 scalar) with M = m_cut + 1 >= n_chunks.
    Rows past n_chunks have length 0 and must be sliced off by the caller.
    """
    m_cut = cutpos.shape[0]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), cutpos + 1])
    row_idx = jnp.arange(m_cut + 1, dtype=jnp.int32)
    cut_ext = jnp.concatenate([cutpos, jnp.full((1,), n - 1, jnp.int32)])
    ends = jnp.where(row_idx < n_cuts, cut_ext[row_idx], jnp.int32(n - 1))
    lens = jnp.maximum(ends - starts + 1, 0)
    # Tail chunk exists unless the last cut landed exactly on byte n-1.
    n_chunks = n_cuts + (jnp.take(starts, n_cuts) < n).astype(jnp.int32)
    return jnp.clip(starts, 0, n), lens, n_chunks


def _pack_rows(flat_u8, starts, lens, *, max_size: int):
    """Gather every chunk of a wave into its fixed-width fingerprint row.

    flat_u8 holds the wave's streams back to back; ``starts`` are absolute
    offsets into it. A row's slice may run past its chunk into the next
    stream: those bytes are masked by ``lens``. Words are packed little
    endian by ``_pack_words`` (the bitcast of a (…, 4) uint8 view pads to
    128 lanes on the TPU and compiles for minutes).
    """
    row_words, width = fp_row_words(max_size)
    row_bytes = row_words * 4
    padded = jnp.pad(flat_u8, (0, row_bytes))
    rows_u8 = jax.vmap(
        lambda s: jax.lax.dynamic_slice(padded, (s,), (row_bytes,))
    )(starts)
    col = jnp.arange(row_bytes, dtype=jnp.int32)
    rows_u8 = jnp.where(col[None, :] < lens[:, None], rows_u8, jnp.uint8(0))
    words = _pack_words(rows_u8)
    m = starts.shape[0]
    return jnp.concatenate(
        [
            words,
            jnp.minimum(lens, row_bytes).astype(jnp.uint32)[:, None],
            jnp.zeros((m, width - row_words - 1), jnp.uint32),
        ],
        axis=1,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mask", "min_size", "max_size", "use_pallas", "interpret", "block_len"
    ),
)
def _cut_and_fp_impl(
    streams, *, mask: int, min_size: int, max_size: int, use_pallas: bool,
    interpret: bool, block_len: int,
):
    lens = [s.shape[0] for s in streams]
    tvs = [jnp.take(_gear_jnp(), s.astype(jnp.int32)) for s in streams]
    if use_pallas or interpret:
        cuts = [
            (pos, nc)
            for _, pos, nc in cdc_cuts_pallas(
                tvs, mask=mask, min_size=min_size, max_size=max_size,
                interpret=interpret, block_len=block_len,
            )
        ]
    else:
        # Per-stream hashing so each stream sees its own zero prefix window,
        # exactly like the kernel's per-stream halo.
        cuts = []
        for tv, n in zip(tvs, lens):
            m = ref.cdc_cut_mask(
                (ref.cdc_hashes(tv) & jnp.uint32(mask)) == 0, n, min_size, max_size
            )
            pos = jnp.nonzero(m, size=max_cuts(n, min_size), fill_value=n)[0]
            cuts.append((pos.astype(jnp.int32), jnp.sum(m).astype(jnp.int32)))
    tables = [_chunk_table(pos, nc, n=n) for (pos, nc), n in zip(cuts, lens)]
    bases = np.cumsum([0] + lens[:-1])
    stacked = _pack_rows(
        jnp.concatenate(streams),
        jnp.concatenate([t[0] + int(b) for t, b in zip(tables, bases)]),
        jnp.concatenate([t[1] for t in tables]),
        max_size=max_size,
    )
    if use_pallas:
        fps = fingerprint_chunks_pallas(stacked)
    else:
        fps = ref.fingerprint_chunks(stacked)
    out, off = [], 0
    for (cutpos, n_cuts), (starts, _, n_chunks) in zip(cuts, tables):
        out.append((cutpos, n_cuts, fps[off : off + starts.shape[0]], n_chunks))
        off += starts.shape[0]
    return out


def cdc_cut_and_fingerprint_many(
    streams: list[jnp.ndarray],
    *,
    mask: int | None = None,
    min_size: int | None = None,
    max_size: int | None = None,
    spec=None,
    use_pallas: bool | None = None,
    interpret: bool = False,
    block_len: int | None = None,
) -> list[tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Chunk + fingerprint a wave of byte streams entirely on device.

    streams: list of (n_i,) uint8 arrays (one per tensor/object). Boundaries
    are bit-identical to ``chunk_cdc_scalar`` with the same mask/min/max;
    fingerprints follow the ``fp_row_words`` row contract. Pass either a
    ``core.chunking.ChunkSpec`` via ``spec=`` (the consolidated surface) or
    the raw mask/min_size/max_size trio (legacy spelling, kept mapped).

    Returns, per stream: (cut_positions (M,) i32 — first ``n_cuts`` valid,
    n_cuts i32 scalar, fps (R, 4) u32 — first ``n_chunks`` rows valid,
    n_chunks i32 scalar). All on device: the caller decides when to sync.
    Exactly one CDC launch + one fingerprint launch per call, regardless of
    wave size (empty streams short-circuit without a launch).
    """
    mask, min_size, max_size = _resolve_chunk_args(spec, mask, min_size, max_size)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if block_len is None:
        block_len = CUT_BLOCK_LEN
    assert min_size >= 1, "pass a normalized ChunkingSpec (min_size >= 1)"
    zero = jnp.zeros((), jnp.int32)
    empty = (
        jnp.zeros((0,), jnp.int32), zero, jnp.zeros((0, 4), jnp.uint32), zero
    )
    nonempty = [s for s in streams if s.shape[0] > 0]
    if not nonempty:
        return [empty for _ in streams]
    _count_launch("cdc")
    _count_launch("fingerprint")
    live = iter(
        _cut_and_fp_impl(
            tuple(nonempty), mask=mask, min_size=min_size, max_size=max_size,
            use_pallas=use_pallas, interpret=interpret, block_len=block_len,
        )
    )
    return [next(live) if s.shape[0] > 0 else empty for s in streams]


def cdc_cut_and_fingerprint(
    stream: jnp.ndarray,
    *,
    mask: int | None = None,
    min_size: int | None = None,
    max_size: int | None = None,
    spec=None,
    **kw,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-stream ``cdc_cut_and_fingerprint_many``."""
    return cdc_cut_and_fingerprint_many(
        [stream], mask=mask, min_size=min_size, max_size=max_size, spec=spec, **kw
    )[0]


def _resolve_chunk_args(
    spec, mask: int | None, min_size: int | None, max_size: int | None
) -> tuple[int, int, int]:
    """Map the consolidated ``ChunkSpec`` spelling onto the kernels' raw
    mask/min/max trio; explicit raw kwargs win over the spec (legacy call
    sites pass only the trio, new ones only ``spec``)."""
    if spec is not None:
        kw = spec.kernel_kwargs()
        mask = kw["mask"] if mask is None else mask
        min_size = kw["min_size"] if min_size is None else min_size
        max_size = kw["max_size"] if max_size is None else max_size
    if mask is None or min_size is None or max_size is None:
        raise TypeError("pass spec= or all of mask/min_size/max_size")
    return mask, min_size, max_size


def cdc_cut_offsets(
    data_u8: jnp.ndarray,
    *,
    mask: int | None = None,
    min_size: int | None = None,
    max_size: int | None = None,
    spec=None,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> np.ndarray:
    """Device cut selection -> host int64 cut positions (inclusive chunk
    ends, tail excluded) — the device twin of ``chunking._cdc_cuts``.
    Accepts ``spec=`` (a ``core.chunking.ChunkSpec``) or the raw trio."""
    mask, min_size, max_size = _resolve_chunk_args(spec, mask, min_size, max_size)
    if use_pallas is None:
        use_pallas = _on_tpu()
    n = int(data_u8.shape[0])
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    _count_launch("cdc")
    tvals = jnp.take(_gear_jnp(), data_u8.astype(jnp.int32))
    if use_pallas or interpret:
        m = cdc_cut_masks_pallas(
            [tvals], mask=mask, min_size=min_size, max_size=max_size,
            interpret=interpret,
        )[0]
    else:
        cand = (ref.cdc_hashes(tvals) & jnp.uint32(mask)) == 0
        m = ref.cdc_cut_mask(cand, n, min_size, max_size)
    return np.flatnonzero(np.asarray(jax.device_get(m)))


# ---------------------------------------------------------------------------
# Byte-bounded save waves: device fingerprints of a whole pytree, one launch
# pair per wave, with each wave's fingerprint rows within wave_row_cap.
# ---------------------------------------------------------------------------


def _row_bytes(spec) -> int:
    """Bytes of one fingerprint row under ``spec`` (a ``ChunkSpec``)."""
    if spec.kind == "cdc":
        return fp_row_words(spec.max_bytes)[1] * 4
    return max(128, spec.target_bytes // 4) * 4


def wave_rows(n: int, spec) -> int:
    """Fingerprint rows an n-byte stream adds to a device wave: one per
    possible chunk. A wave's temporaries scale with its rows."""
    if spec.kind == "cdc":
        return max_cuts(n, spec.min_bytes) + 1
    return -(-n // _row_bytes(spec))


def wave_row_cap(spec) -> int:
    """Rows one wave may hold: WAVE_ROW_BYTES of rows, and at most
    WAVE_MAX_ROWS so the cut kernel's SMEM tables stay small."""
    return max(1, min(WAVE_ROW_BYTES // _row_bytes(spec), WAVE_MAX_ROWS))


def segment_bytes(spec) -> int:
    """Largest stream whose rows fill one wave alone. Larger leaves are cut
    into segments of at most this many bytes."""
    cap = wave_row_cap(spec)
    if spec.kind == "cdc":
        return max(1, (cap - 2) * (spec.min_bytes + 1) + spec.min_bytes)
    return cap * _row_bytes(spec)


def _unit_bytes(leaf) -> int:
    """Bytes per slicing unit: a row of the leaf's (-1, last dim) view, or
    an element of a 0-D or 1-D leaf."""
    return (leaf.shape[-1] if leaf.ndim >= 2 else 1) * leaf.dtype.itemsize


def plan_waves(leaves, spec) -> list[list[tuple[int, int, int]]]:
    """Pack the leaves' segments into device waves.

    A segment is (leaf index, first unit, unit count) in ``_unit_bytes``
    units, so slicing one never copies the whole leaf. Segments hold at
    most ``segment_bytes(spec)`` (one unit at least). A wave takes segments
    in leaf order while their ``wave_rows`` sum stays within
    ``wave_row_cap(spec)``; a lone segment always forms a wave.
    """
    seg_max = segment_bytes(spec)
    cap = wave_row_cap(spec)
    waves: list[list[tuple[int, int, int]]] = []
    cur: list[tuple[int, int, int]] = []
    cur_rows = 0
    for i, leaf in enumerate(leaves):
        if leaf.size == 0:
            continue
        unit = _unit_bytes(leaf)
        n_units = leaf.size * leaf.dtype.itemsize // unit
        step = max(1, seg_max // unit)
        for a in range(0, n_units, step):
            k = min(step, n_units - a)
            cost = wave_rows(k * unit, spec)
            if cur and cur_rows + cost > cap:
                waves.append(cur)
                cur, cur_rows = [], 0
            cur.append((i, a, k))
            cur_rows += cost
    if cur:
        waves.append(cur)
    return waves


@functools.partial(jax.jit, static_argnames=("sizes",))
def _segments(leaves, starts, *, sizes):
    """A wave's segments as flat arrays, in a program of their own: segment
    j is ``sizes[j]`` units of ``leaves[j]`` from unit ``starts[j]`` on.
    With the slices fused into the byte split of ``tensor_to_u8``, the wave
    programs of the qwen2.5-32b 2-layer tree took 257.8 s to compile for
    v5e, against 70.1 s apart."""
    out = []
    for x, a, k in zip(leaves, starts, sizes):
        view = x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(-1)
        out.append(jax.lax.dynamic_slice_in_dim(view, a, k).reshape(-1))
    return out


@functools.partial(jax.jit, static_argnames=("spec", "use_pallas"))
def _wave_impl(segs, *, spec, use_pallas: bool):
    """One save wave as one program: fingerprint each flat segment's
    chunks, content-defined (fused cut + fingerprint) or fixed-size.
    Returns (fps, n_chunks) per segment. Waves of equal segment lengths
    share one compilation."""
    if spec.kind == "cdc":
        res = _cut_and_fp_impl(
            tuple(jax.lax.optimization_barrier(tensor_to_u8(s)) for s in segs),
            **spec.kernel_kwargs(),
            use_pallas=use_pallas, interpret=False, block_len=CUT_BLOCK_LEN,
        )
        return [(fps, n_chunks) for _, _, fps, n_chunks in res]
    chunk_words = _row_bytes(spec) // 4
    rows = [_word_rows(tensor_to_u32(s), chunk_words) for s in segs]
    stacked = jnp.concatenate(rows)
    fps = fingerprint_chunks_pallas(stacked) if use_pallas else ref.fingerprint_chunks(stacked)
    bounds = np.cumsum([0] + [r.shape[0] for r in rows])
    return [(fps[a:b], b - a) for a, b in zip(bounds[:-1], bounds[1:])]


def leaf_fingerprints(leaves, spec) -> list[bytes]:
    """Device fingerprint bytes of each leaf, for change detection.

    Runs the waves of ``plan_waves``: each slices its segments, then runs
    ONE fused CDC launch plus ONE fingerprint launch (CDC spec) or ONE
    fingerprint launch (fixed spec), then one ``device_get``. Each wave is
    a ``ckpt.wave`` span, split into ``ckpt.wave.dispatch`` (slicing and
    the wave program enqueued), ``ckpt.wave.fetch`` (the host waits on the
    device) and ``ckpt.wave.unpack``. A leaf's bytes are its segments'
    chunk fingerprints in order. CDC boundaries restart at segment edges,
    so fingerprints compare only under the same spec.
    """
    leaves = [x if isinstance(x, jax.Array) else jnp.asarray(x) for x in leaves]
    out: list[list[bytes]] = [[] for _ in leaves]
    for w, wave in enumerate(plan_waves(leaves, spec)):
        nbytes = sum(k * _unit_bytes(leaves[i]) for i, _, k in wave)
        with span("ckpt.wave", index=w, segments=len(wave), bytes=nbytes):
            if spec.kind == "cdc":
                _count_launch("cdc")
            _count_launch("fingerprint")
            with span("ckpt.wave.dispatch"):
                segs = _segments(
                    [leaves[i] for i, _, _ in wave], [a for _, a, _ in wave],
                    sizes=tuple(k for _, _, k in wave),
                )
                res = _wave_impl(segs, spec=spec, use_pallas=_on_tpu())
            with span("ckpt.wave.fetch"):
                res = jax.device_get(res)
            with span("ckpt.wave.unpack"):
                for (i, _, _), (fps, nc) in zip(wave, res):
                    out[i].append(np.asarray(fps)[: int(nc)].tobytes())
    return [b"".join(p) for p in out]
