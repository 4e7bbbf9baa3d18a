"""CDC-chunked checkpointing: dedup robust to byte-shifts (insertions).

Fixed-size chunking loses all dedup after a small prefix insertion shifts
every boundary; content-defined chunking re-synchronizes — this matters for
checkpoint streams whose serialization layout can shift (e.g. a metadata
header that grows by a few bytes between framework versions)."""

import os

import pytest

from repro.core import ChunkingSpec, DedupCluster


def _savings_after_shift(kind: str) -> float:
    spec = ChunkingSpec(kind, 2048)
    c = DedupCluster.create(4, chunking=spec)
    # 96 KiB is ~48 CDC chunks — plenty to show re-synchronization while
    # keeping the fixture small (the chunker itself is vectorized now).
    body = os.urandom(96 * 1024)
    c.write_object("v1", b"HDR1" + body)
    c.write_object("v2", b"HEADER-GREW-BY-SOME-BYTES" + body)
    return c.space_savings()


def test_cdc_survives_insertion_fixed_does_not():
    fixed = _savings_after_shift("fixed")
    cdc = _savings_after_shift("cdc")
    assert fixed < 0.05, f"fixed-size chunking should lose dedup, got {fixed:.2f}"
    assert cdc > 0.35, f"CDC should recover dedup past the shift, got {cdc:.2f}"


def test_cdc_chunk_boundaries_deterministic():
    from repro.core.chunking import chunk_object

    spec = ChunkingSpec("cdc", 1024)
    data = os.urandom(32 * 1024)
    a = chunk_object(data, spec)
    b = chunk_object(data, spec)
    assert [len(x) for x in a] == [len(x) for x in b]
    assert b"".join(a) == data


def test_checkpointer_one_launch_pair_per_save():
    """The fused device pipeline must do exactly ONE CDC launch + ONE
    fingerprint launch per save wave, no matter how many leaves the wave
    holds — and the counters must surface in DedupCheckpointer.stats. This
    tree fits one wave, so a save is one launch pair."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointConfig, DedupCheckpointer

    cluster = DedupCluster.create(3, chunking=ChunkingSpec("fixed", 16 * 1024))
    ckpt = DedupCheckpointer(
        cluster, CheckpointConfig(fp_chunk_bytes=4096, device_cdc=True)
    )
    tree = {
        "w": jnp.arange(12_000, dtype=jnp.float32),
        "b": jnp.ones((257,), jnp.bfloat16),
        "step": 3,  # non-array leaf: must not add launches
        "emb": jnp.arange(5_000, dtype=jnp.int32),
    }
    assert ckpt.stats["cdc_launches"] == 0 and ckpt.stats["fp_launches"] == 0
    ckpt.save("s1", tree)
    assert ckpt.stats["cdc_launches"] == 1
    assert ckpt.stats["fp_launches"] == 1
    # second save of an identical tree: one more launch pair, all array
    # leaves ref-only
    ckpt.save("s2", tree)
    assert ckpt.stats["cdc_launches"] == 2
    assert ckpt.stats["fp_launches"] == 2
    assert ckpt.stats["leaves_ref_only"] == 3
    # legacy fixed-size route still books exactly one fingerprint launch
    ckpt2 = DedupCheckpointer(
        cluster, CheckpointConfig(fp_chunk_bytes=4096, device_cdc=False)
    )
    ckpt2.save("s3", tree)
    assert ckpt2.stats["cdc_launches"] == 0
    assert ckpt2.stats["fp_launches"] == 1


def _ckpt(**cfg):
    from repro.checkpoint import CheckpointConfig, DedupCheckpointer

    cluster = DedupCluster.create(3, chunking=ChunkingSpec("fixed", 16 * 1024))
    return DedupCheckpointer(cluster, CheckpointConfig(**cfg))


def test_kernel_error_raises_out_of_save(monkeypatch):
    """A failing device kernel must surface, not degrade into full writes."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    def boom(*a, **k):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(kops, "_wave_impl", boom)
    ckpt = _ckpt(fp_chunk_bytes=4096, device_cdc=True)
    with pytest.raises(RuntimeError, match="kernel refused"):
        ckpt.save("s1", {"w": jnp.arange(1000, dtype=jnp.float32)})
    assert ckpt.stats["leaves_written"] == 0


@pytest.mark.parametrize("leaf", ["int", "complex64", "float64_numpy"])
def test_untaken_leaf_types_write_in_full(leaf):
    """Leaves the kernels do not take, by type, skip the device fast path:
    no launch, a full write on every save, and an exact restore."""
    pytest.importorskip("jax")
    import numpy as np

    from repro.checkpoint.dedup_ckpt import _device_leaf

    value = {
        "int": 7,
        "complex64": np.arange(6, dtype=np.complex64),
        "float64_numpy": np.linspace(0, 1, 9),
    }[leaf]
    assert not _device_leaf(value)
    ckpt = _ckpt(fp_chunk_bytes=4096, device_cdc=True)
    ckpt.save("s1", {"x": value})
    ckpt.save("s2", {"x": value})
    assert ckpt.stats["cdc_launches"] == 0 and ckpt.stats["fp_launches"] == 0
    assert ckpt.stats["leaves_ref_only"] == 0
    assert ckpt.stats["leaves_written"] == 2
    np.testing.assert_array_equal(np.asarray(ckpt.restore("s2")["['x']"]), value)


def test_large_leaves_split_into_bounded_waves(monkeypatch):
    """With a small wave budget, big leaves are cut into segments and the
    tree runs as several waves: one launch pair per wave, every wave's
    rows within budget (or a lone segment), and change detection still
    exact per leaf — a change in one segment rewrites the whole leaf."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from repro.core.chunking import ChunkSpec
    from repro.kernels import ops as kops

    spec = ChunkSpec.for_checkpoint(1024)
    _, width = kops.fp_row_words(spec.max_bytes)
    monkeypatch.setattr(kops, "WAVE_ROW_BYTES", 12 * width * 4)
    cap = kops.wave_row_cap(spec)
    assert cap == 12
    seg = kops.segment_bytes(spec)
    assert kops.wave_rows(seg, spec) <= cap < kops.wave_rows(seg + 1, spec)

    rng = np.random.default_rng(5)
    tree = {
        "big": jnp.asarray(rng.standard_normal((64, 160)), jnp.float32),
        "flat": jnp.asarray(rng.integers(0, 2**16, 9000, dtype=np.uint16)).view(jnp.bfloat16),
        "small": jnp.arange(10, dtype=jnp.int32),
    }
    leaves = list(tree.values())
    waves = kops.plan_waves(leaves, spec)
    assert len(waves) > 2
    for w in waves:
        rows = sum(kops.wave_rows(k * kops._unit_bytes(leaves[i]), spec) for i, _, k in w)
        assert rows <= cap or len(w) == 1
    covered = {i: sum(k for j, _, k in (s for w in waves for s in w) if j == i) for i in range(3)}
    assert covered == {0: 64, 1: 9000, 2: 10}

    ckpt = _ckpt(chunk_spec=spec)
    ckpt.save("s1", tree)
    assert ckpt.stats["cdc_launches"] == ckpt.stats["fp_launches"] == len(waves)
    tree2 = dict(tree, big=tree["big"].at[63, 5].add(1.0))
    ckpt.save("s2", tree2)
    assert ckpt.stats["cdc_launches"] == 2 * len(waves)
    assert ckpt.stats["leaves_ref_only"] == 2      # flat + small
    for name, t in (("s1", tree), ("s2", tree2)):
        got = ckpt.restore(name, like=t)
        for k in t:
            np.testing.assert_array_equal(
                np.asarray(got[k]).view(np.uint8), np.asarray(t[k]).view(np.uint8)
            )
