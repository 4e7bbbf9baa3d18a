"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle vs
host reference."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core.chunking import GEAR_TABLE, window_hash_at
from repro.kernels import ops, ref
from repro.kernels.cdc import cdc_hashes_pallas
from repro.kernels.fingerprint import fingerprint_chunks_pallas

RNG = np.random.default_rng(42)


@pytest.mark.parametrize(
    "shape",
    [(1, 128), (2, 129), (5, 511), (8, 512), (13, 1000), (256, 512), (300, 700), (257, 513)],
)
def test_fingerprint_pallas_matches_ref(shape):
    x = jnp.asarray(RNG.integers(0, 2**32, size=shape, dtype=np.uint32))
    r = np.asarray(ref.fingerprint_chunks(x))
    p = np.asarray(fingerprint_chunks_pallas(x, interpret=True))
    np.testing.assert_array_equal(r, p)


@pytest.mark.parametrize("tc,tw", [(8, 128), (64, 256), (256, 512)])
def test_fingerprint_pallas_tile_invariance(tc, tw):
    x = jnp.asarray(RNG.integers(0, 2**32, size=(70, 600), dtype=np.uint32))
    r = np.asarray(ref.fingerprint_chunks(x))
    p = np.asarray(fingerprint_chunks_pallas(x, interpret=True, tile_chunks=tc, tile_words=tw))
    np.testing.assert_array_equal(r, p)


def test_fingerprint_rows_independent():
    x = jnp.asarray(RNG.integers(0, 2**32, size=(4, 256), dtype=np.uint32))
    full = np.asarray(ref.fingerprint_chunks(x))
    for i in range(4):
        row = np.asarray(ref.fingerprint_chunks(x[i : i + 1]))
        np.testing.assert_array_equal(full[i], row[0])


def test_fingerprint_avalanche():
    """Single-bit flips must change most output bits (mix quality)."""
    x = jnp.asarray(RNG.integers(0, 2**32, size=(1, 256), dtype=np.uint32))
    base = np.asarray(ref.fingerprint_chunks(x))[0]
    flipped_bits = []
    for trial in range(16):
        xi = np.array(x)
        xi[0, trial * 16] ^= 1 << (trial % 32)
        out = np.asarray(ref.fingerprint_chunks(jnp.asarray(xi)))[0]
        diff = np.bitwise_xor(base, out)
        flipped_bits.append(sum(bin(int(w)).count("1") for w in diff))
    assert np.mean(flipped_bits) > 40, np.mean(flipped_bits)  # ~64 expected of 128


def test_fingerprint_no_collisions_bulk():
    x = jnp.asarray(RNG.integers(0, 2**32, size=(2000, 64), dtype=np.uint32))
    fps = np.asarray(ref.fingerprint_chunks(x))
    assert len({tuple(r) for r in fps}) == 2000


@pytest.mark.parametrize("n", [33, 256, 2048, 5000, 16384])
def test_cdc_pallas_matches_ref_and_host(n):
    data = RNG.integers(0, 256, size=n, dtype=np.uint8)
    tv = jnp.take(jnp.asarray(np.array(GEAR_TABLE, dtype=np.uint32)),
                  jnp.asarray(data).astype(jnp.int32))
    r = np.asarray(ref.cdc_hashes(tv))
    p = np.asarray(cdc_hashes_pallas(tv, interpret=True))
    np.testing.assert_array_equal(r, p)
    b = bytes(data)
    for i in [0, 1, 31, 32, n // 3, n - 1]:
        assert int(r[i]) == window_hash_at(b, i)


@pytest.mark.parametrize("kind", ["every-byte", "random"])
def test_gear_values_match_table(kind):
    """The device computes each byte's gear value from the table's
    generating arithmetic: it must equal ``GEAR_TABLE`` bit for bit."""
    if kind == "every-byte":
        data = np.arange(256, dtype=np.uint8)
    else:
        data = RNG.integers(0, 256, size=5000, dtype=np.uint8)
    got = np.asarray(ops.gear_values(jnp.asarray(data)))
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got, np.take(np.array(GEAR_TABLE, np.uint32), data))


def test_gear_values_lower_without_a_table():
    """No gather and no 256-entry table constant on the CDC path: a per-byte
    table lookup is a gather on the TPU, and took 49 s of a 5.07 GB save on
    v5e. ``_pack_rows``'s slices are gathers too, so the wave is checked
    for the table constant only."""
    assert "gather" not in jax.jit(ops.gear_values).lower(
        jnp.zeros((4096,), jnp.uint8)
    ).as_text()
    wave = ops._cut_and_fp_impl.lower(
        (jnp.zeros((3000,), jnp.uint8), jnp.zeros((999,), jnp.uint8)),
        mask=255, min_size=64, max_size=512, use_pallas=False,
        interpret=False, block_len=512,
    ).as_text()
    assert not re.search(r"stablehlo\.constant dense<.*> : tensor<256xui32>", wave)


def test_cdc_boundary_mask():
    data = RNG.integers(0, 256, size=4096, dtype=np.uint8)
    mask = (1 << 8) - 1
    bounds = np.asarray(ops.cdc_boundaries(jnp.asarray(data), mask, use_pallas=False))
    frac = bounds.mean()
    assert 1 / 1024 < frac < 1 / 64  # ~1/256 expected


@pytest.mark.parametrize(
    "dtype,shape",
    [
        ("uint8", (7,)), ("uint8", (128,)), ("uint8", (3, 5)),
        ("bfloat16", (33,)), ("bfloat16", (16, 16)),
        ("float16", (9,)), ("float16", (64,)),
        ("float32", (1,)), ("float32", (17, 3)),
        ("float64", (5,)), ("float64", (8, 8)),
        ("int64", (3,)), ("int64", (31,)),
        ("bool", (13,)),
    ],
)
def test_tensor_to_u32_matches_numpy_bytes(dtype, shape):
    """tensor_to_u32 must pack the tensor's raw little-endian bytes into
    uint32 words — exactly np.frombuffer(arr.tobytes() + pad, '<u4') — for
    every dtype, including the wide (f64/i64) and sub-word (u8/bool) paths."""
    with jax.enable_x64(True):
        n = int(np.prod(shape))
        if dtype == "bool":
            host = (RNG.integers(0, 2, size=shape) > 0)
            t = jnp.asarray(host)
        elif dtype == "bfloat16":
            host16 = RNG.integers(0, 2**16, size=shape, dtype=np.uint16)
            t = jnp.asarray(host16).view(jnp.bfloat16)
            host = np.asarray(jax.device_get(t))
        elif np.issubdtype(np.dtype(dtype), np.integer):
            info = np.iinfo(dtype)
            host = RNG.integers(info.min, info.max, size=shape, dtype=dtype)
            t = jnp.asarray(host)
        else:
            host = RNG.standard_normal(n).reshape(shape).astype(dtype)
            t = jnp.asarray(host)
        raw = (host.astype(np.uint8) if dtype == "bool" else host).tobytes()
        padded = raw + b"\0" * ((-len(raw)) % 4)
        exp = np.frombuffer(padded, "<u4")
        got = np.asarray(jax.device_get(ops.tensor_to_u32(t)))
        np.testing.assert_array_equal(got, exp)
        # and the u8 view must be the raw bytes themselves (unpadded)
        got8 = np.asarray(jax.device_get(ops.tensor_to_u8(t)))
        np.testing.assert_array_equal(got8, np.frombuffer(raw, np.uint8))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8, jnp.float16])
def test_tensor_fingerprint_dtypes(dtype):
    t = jnp.asarray(RNG.standard_normal((32, 64)) * 10).astype(dtype)
    fps = ops.fingerprint_tensor_chunks(t, chunk_bytes=2048, use_pallas=False)
    fps2 = ops.fingerprint_tensor_chunks(t, chunk_bytes=2048, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(fps), np.asarray(fps2))
    # perturb one element -> some fingerprint changes
    t2 = t.at[3, 5].set(t[3, 5] + jnp.asarray(1, dtype))
    fps3 = ops.fingerprint_tensor_chunks(t2, chunk_bytes=2048, use_pallas=False)
    assert not np.array_equal(np.asarray(fps), np.asarray(fps3))


def test_tensor_fingerprint_pallas_path_matches_ref_path():
    t = jnp.asarray(RNG.standard_normal((64, 128)), dtype=jnp.float32)
    a = ops.fingerprint_tensor_chunks(t, chunk_bytes=4096, use_pallas=False)
    # use_pallas=True on CPU -> falls to pallas interpret through jit? The
    # wrapper compiles pallas only on TPU; emulate via direct interpret call:
    from repro.kernels.ops import tensor_to_u32
    flat = tensor_to_u32(t)
    words = jnp.pad(flat, (0, (-flat.shape[0]) % 1024)).reshape(-1, 1024)
    b = fingerprint_chunks_pallas(words, interpret=True)
    r = ref.fingerprint_chunks(words)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(r))
    assert np.asarray(a).shape[1] == 4
