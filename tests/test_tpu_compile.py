"""Compile the checkpoint path's kernels for one TPU v5e chip, without one.

The TPU compiler ships with jax and compiles for a described topology, so
these tests catch what interpret mode cannot: primitives Mosaic does not
lower, block shapes off the (8, 128) tiling, SMEM or HBM overruns. Nothing
runs; results are checked by the interpret-mode suites and on the chip by
``chip_smoke.py``.
"""

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.chunking import ChunkSpec
from repro.kernels import ops
from repro.kernels.cdc import CUT_BLOCK_LEN, cdc_cut_masks_pallas, cdc_hashes_pallas
from repro.kernels.fingerprint import fingerprint_chunks_pallas

HBM_BYTES = 16 * 2**30          # one v5e chip
CKPT_SPEC = ChunkSpec.for_checkpoint(512 * 1024)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    return compiled, mem.temp_size_in_bytes + mem.argument_size_in_bytes


def test_fingerprint_kernel_compiles(one_chip):
    words = jax.ShapeDtypeStruct((4096, 131072), jnp.uint32, sharding=one_chip)
    compiled, _ = _compile(fingerprint_chunks_pallas, words)
    assert "tpu_custom_call" in compiled.as_text()


def test_window_hash_kernel_compiles(one_chip):
    tvals = jax.ShapeDtypeStruct((16 * 2**20,), jnp.uint32, sharding=one_chip)
    compiled, _ = _compile(cdc_hashes_pallas, tvals)
    assert "tpu_custom_call" in compiled.as_text()


def test_cut_kernel_compiles_two_stream_wave(one_chip):
    kw = CKPT_SPEC.kernel_kwargs()
    a = jax.ShapeDtypeStruct((3 * 2**20 + 5,), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((2**20,), jnp.uint32, sharding=one_chip)
    compiled, _ = _compile(lambda x, y: cdc_cut_masks_pallas([x, y], **kw), a, b)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_wave_fits_chip_at_budget(one_chip):
    """One full segment is the largest wave the checkpointer launches."""
    n = ops.segment_bytes(CKPT_SPEC)
    stream = jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
    compiled, used = _compile(
        lambda s: ops._cut_and_fp_impl(
            (s,), **CKPT_SPEC.kernel_kwargs(), use_pallas=True,
            interpret=False, block_len=CUT_BLOCK_LEN,
        ),
        stream,
    )
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert used < HBM_BYTES // 4, used


def test_save_wave_of_embedding_fits_chip(one_chip):
    """A full-segment save wave over the qwen2.5-32b embedding: slicing the
    segment and viewing it as bytes must neither copy the leaf nor pad a
    byte view to 128 lanes."""
    table = jax.ShapeDtypeStruct((152064, 5120), jnp.bfloat16, sharding=one_chip)
    rows = ops.segment_bytes(CKPT_SPEC) // ops._unit_bytes(table)
    sliced = ops._segments.lower([table], [0], sizes=(rows,)).compile()
    mem = sliced.memory_analysis()
    # The segment and at most one copy of it, never the 1.56 GB leaf.
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes <= 2 * rows * ops._unit_bytes(table)
    seg = jax.ShapeDtypeStruct((rows * table.shape[1],), table.dtype, sharding=one_chip)
    compiled = ops._wave_impl.lower([seg], spec=CKPT_SPEC, use_pallas=True).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES // 4


def _kernel_names(text: str) -> list[str]:
    """Names of the Pallas kernels in a compiled program's HLO text, without
    their ``.N`` suffix."""
    return sorted(
        line.split("=", 1)[0].strip().rsplit(".", 1)[0]
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    )


@pytest.mark.parametrize("program, want", [
    ("wave", ["%cdc_cuts_pallas", "%fingerprint_chunks_pallas"]),
    ("hashes", ["%cdc_hashes_pallas"]),
])
def test_kernels_keep_their_names(one_chip, program, want):
    """Each kernel's op carries its own name, whatever jitted function holds
    it: device traces show ops by these names, and the wave program as
    module ``jit__wave_impl``."""
    if program == "wave":
        seg = jax.ShapeDtypeStruct((2**20,), jnp.bfloat16, sharding=one_chip)
        compiled = ops._wave_impl.lower([seg], spec=CKPT_SPEC, use_pallas=True).compile()
        assert compiled.as_text().startswith("HloModule jit__wave_impl,")
    else:
        tvals = jax.ShapeDtypeStruct((2**20,), jnp.uint32, sharding=one_chip)
        compiled, _ = _compile(cdc_hashes_pallas, tvals)
    assert _kernel_names(compiled.as_text()) == want
