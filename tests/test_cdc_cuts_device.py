"""Device-resident CDC cut selection: the fused Pallas kernel (interpret
mode) and its jnp oracle must produce cut lists BYTE-IDENTICAL to the scalar
reference ``chunk_cdc_scalar`` for any stream and any ``ChunkingSpec`` —
including the ``hard = max(lo, start + max_size - 1)`` forced-cut edge and
stream tails shorter than ``min_size`` — and the fused per-chunk
fingerprints must match the host-built row oracle.

Two layers: a seeded sweep that always runs (no external deps), and a
hypothesis property suite when hypothesis is installed (CI installs it).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core.chunking import (
    ChunkingSpec,
    cdc_mask,
    chunk_cdc,
    chunk_cdc_scalar,
)
from repro.core.fingerprint import fingerprint_many
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.cdc import cdc_cut_masks_pallas

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI always has hypothesis
    HAVE_HYPOTHESIS = False

# Every byte value, then runs of 0x00 and 0xFF longer than the max_size of
# the cases that use it (so they end in forced cuts), and every value again.
ALL_BYTES = (
    bytes(range(256)) * 3 + b"\x00" * 1500 + bytes(range(255, -1, -1))
    + b"\xff" * 1500 + bytes(range(256))
)


def _scalar_cuts(data: bytes, spec: ChunkingSpec) -> np.ndarray:
    """Inclusive chunk-end positions (tail excluded), via the scalar loop
    itself (chunk lengths alone cannot distinguish a final cut from a tail)."""
    cuts = []
    spec = spec.normalized()
    mask = cdc_mask(spec.chunk_size)
    start, i, n = 0, spec.min_size, len(data)
    from repro.core.chunking import window_hash_at

    while i < n:
        if (window_hash_at(data, i) & mask) == 0 or (i - start + 1) >= spec.max_size:
            cuts.append(i)
            start = i + 1
            i = start + spec.min_size
        else:
            i += 1
    return np.asarray(cuts, dtype=np.int64)


def _device_cuts(data: bytes, spec: ChunkingSpec, *, interpret: bool, block_len=512):
    spec = spec.normalized()
    mask = cdc_mask(spec.chunk_size)
    tv = kops.gear_values(jnp.asarray(np.frombuffer(data, np.uint8)))
    if interpret:
        m = cdc_cut_masks_pallas(
            [tv], mask=mask, min_size=spec.min_size, max_size=spec.max_size,
            interpret=True, block_len=block_len,
        )[0]
    else:
        cand = (ref.cdc_hashes(tv) & jnp.uint32(mask)) == 0
        m = ref.cdc_cut_mask(cand, len(data), spec.min_size, spec.max_size)
    return np.flatnonzero(np.asarray(m))


def _host_fp_rows(chunks: list[bytes], max_size: int) -> np.ndarray:
    """Numpy oracle for the fused fingerprint row contract (fp_row_words)."""
    row_words, width = kops.fp_row_words(max_size)
    rows = np.zeros((len(chunks), width), np.uint32)
    for i, c in enumerate(chunks):
        b = c + b"\0" * (row_words * 4 - len(c))
        rows[i, :row_words] = np.frombuffer(b, "<u4")
        rows[i, row_words] = len(c)
    return rows


def _check_spec(data: bytes, spec: ChunkingSpec, *, interpret: bool) -> None:
    exp = _scalar_cuts(data, spec)
    got = _device_cuts(data, spec, interpret=interpret)
    np.testing.assert_array_equal(got, exp)


# --------------------------------------------------------------- seeded sweep

SWEEP = [
    # (n, target, min_size, max_size) — n is a length of random bytes or a
    # stream itself; 0 sizes mean "let normalized() pick"
    (3000, 256, 64, 1024),
    (4096, 64, 1, 97),
    (100, 1024, 60, 4096),      # whole stream shorter than min_size window
    (1, 16, 1, 8),
    (777, 32, 31, 33),
    (2048, 128, 100, 101),      # max_size == min_size + 1: hard-cut dominated
    (1500, 64, 50, 50),         # max_size == min_size: hard = lo always
    (5000, 512, 0, 0),
    pytest.param(ALL_BYTES, 64, 32, 700, id="all-bytes"),
]


@pytest.mark.parametrize("n,target,mn,mx", SWEEP)
def test_device_cuts_match_scalar_oracle(n, target, mn, mx):
    if isinstance(n, bytes):
        data = n
    else:
        rng = np.random.default_rng(n * 31 + target)
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
    spec = ChunkingSpec("cdc", target, mn, mx)
    _check_spec(data, spec, interpret=False)
    _check_spec(data, spec, interpret=True)


def test_device_cuts_low_entropy_forced_cuts():
    """Constant bytes have (almost) no candidates: every cut is a max-size
    hard cut, including the hard = max(lo, start+max_size-1) lower clamp."""
    data = b"\x42" * 3000
    spec = ChunkingSpec("cdc", 128, 100, 300)
    assert len(_scalar_cuts(data, spec)) > 0
    _check_spec(data, spec, interpret=False)
    _check_spec(data, spec, interpret=True)


def test_device_cuts_tail_shorter_than_min():
    """Stream whose last chunk is a tail < min_size (never emitted as a cut)."""
    rng = np.random.default_rng(9)
    spec = ChunkingSpec("cdc", 64, 48, 256)
    for extra in (1, 7, 47):
        base = rng.integers(0, 256, size=1024, dtype=np.uint8).tobytes()
        cuts = _scalar_cuts(base, spec)
        if cuts.size == 0:
            continue
        data = base[: int(cuts[-1]) + 1 + extra]  # tail of exactly `extra` B
        _check_spec(data, spec, interpret=False)
        _check_spec(data, spec, interpret=True)


def test_chunk_cdc_device_backend_bit_identical():
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, size=40 * 1024, dtype=np.uint8).tobytes()
    spec = ChunkingSpec("cdc", 1024)
    dev = list(chunk_cdc(data, spec, backend="device"))
    assert dev == list(chunk_cdc_scalar(data, spec))
    assert b"".join(dev) == data
    # identical bytes => identical canonical fingerprints
    assert fingerprint_many(dev) == fingerprint_many(chunk_cdc_scalar(data, spec))


@pytest.mark.parametrize("interpret, all_bytes", [
    pytest.param(False, False, id="False"),
    pytest.param(True, False, id="True"),
    pytest.param(False, True, id="all-bytes-False"),
    pytest.param(True, True, id="all-bytes-True"),
])
def test_fused_fingerprints_match_host_rows(interpret, all_bytes):
    rng = np.random.default_rng(23)
    spec = ChunkingSpec("cdc", 256, 64, 700)
    streams = [rng.integers(0, 256, size=n, dtype=np.uint8) for n in (3000, 64, 1, 517)]
    if all_bytes:
        streams = [np.frombuffer(ALL_BYTES, np.uint8), streams[1]]
    res = kops.cdc_cut_and_fingerprint_many(
        [jnp.asarray(s) for s in streams],
        mask=cdc_mask(spec.chunk_size),
        min_size=spec.min_size, max_size=spec.max_size,
        use_pallas=False, interpret=interpret, block_len=512,
    )
    for s, (cutpos, n_cuts, fps, n_chunks) in zip(streams, res):
        chunks = list(chunk_cdc_scalar(s.tobytes(), spec))
        assert int(n_chunks) == len(chunks)
        ends = np.cumsum([len(c) for c in chunks]) - 1
        np.testing.assert_array_equal(np.asarray(cutpos)[: int(n_cuts)], ends[: int(n_cuts)])
        exp = np.asarray(ref.fingerprint_chunks(jnp.asarray(_host_fp_rows(chunks, spec.max_size))))
        np.testing.assert_array_equal(np.asarray(fps)[: int(n_chunks)], exp)


def test_fused_one_launch_per_wave():
    rng = np.random.default_rng(29)
    streams = [jnp.asarray(rng.integers(0, 256, size=n, dtype=np.uint8)) for n in (2048, 999)]
    before = kops.launch_snapshot()
    kops.cdc_cut_and_fingerprint_many(
        streams, mask=255, min_size=64, max_size=512, use_pallas=False
    )
    after = kops.launch_snapshot()
    assert after["cdc"] - before["cdc"] == 1
    assert after["fingerprint"] - before["fingerprint"] == 1


def test_fused_empty_wave_no_launch():
    before = kops.launch_snapshot()
    res = kops.cdc_cut_and_fingerprint_many(
        [jnp.zeros((0,), jnp.uint8)], mask=255, min_size=64, max_size=512,
        use_pallas=False,
    )
    assert kops.launch_snapshot() == before
    assert int(res[0][3]) == 0


# ----------------------------------------------------------------- hypothesis


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        data=st.binary(min_size=0, max_size=2500),
        target=st.sampled_from([16, 32, 64, 256, 1024]),
        min_size=st.integers(1, 80),
        extra=st.integers(0, 400),
        entropy=st.sampled_from(["random", "zero", "repeat8"]),
    )
    def test_property_device_cuts_byte_identical(data, target, min_size, extra, entropy):
        if entropy == "zero":
            data = b"\x00" * len(data)
        elif entropy == "repeat8":
            data = (data[:8] or b"\x07") * (len(data) // 8 + 1)
        spec = ChunkingSpec("cdc", target, min_size, max(min_size, min_size + extra))
        if not data:
            assert list(chunk_cdc_scalar(data, spec)) == []
            return
        _check_spec(data, spec, interpret=False)
        _check_spec(data, spec, interpret=True)

    @settings(max_examples=15, deadline=None)
    @given(
        sizes=st.lists(st.integers(1, 1200), min_size=1, max_size=5),
        seed=st.integers(0, 2**16),
        target=st.sampled_from([64, 256]),
    )
    def test_property_fused_wave_matches_scalar(sizes, seed, target):
        """Whole-wave fusion: every stream's cuts and fingerprints must match
        the per-stream scalar oracle — no cross-stream hash or carry
        leakage."""
        rng = np.random.default_rng(seed)
        spec = ChunkingSpec("cdc", target).normalized()
        streams = [rng.integers(0, 256, size=n, dtype=np.uint8) for n in sizes]
        res = kops.cdc_cut_and_fingerprint_many(
            [jnp.asarray(s) for s in streams],
            mask=cdc_mask(spec.chunk_size),
            min_size=spec.min_size, max_size=spec.max_size,
            use_pallas=False, interpret=True, block_len=256,
        )
        for s, (cutpos, n_cuts, fps, n_chunks) in zip(streams, res):
            chunks = list(chunk_cdc_scalar(s.tobytes(), spec))
            assert int(n_chunks) == len(chunks)
            ends = np.cumsum([len(c) for c in chunks]) - 1
            np.testing.assert_array_equal(
                np.asarray(cutpos)[: int(n_cuts)], ends[: int(n_cuts)]
            )
            exp = np.asarray(
                ref.fingerprint_chunks(jnp.asarray(_host_fp_rows(chunks, spec.max_size)))
            )
            np.testing.assert_array_equal(np.asarray(fps)[: int(n_chunks)], exp)
