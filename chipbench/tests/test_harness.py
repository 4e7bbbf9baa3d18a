"""CPU rehearsal of every cell at a tiny size, the control, and the faults
``correct`` must catch. Not part of the repository's tier-1 tests; run with

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.control import readings
from chipbench.tests.tiny import cells, tiny_parts

CELLS = cells()
FROZEN = "qwen2.5-32b-2L.frozen-save"
SEED = 2**31 + 12345


def run_tiny(cell: str, seconds: float = 1.0, trace: bool = False, parts=None) -> dict:
    return harness.run(cell, SEED, seconds, trace, time.perf_counter(),
                       parts=parts or tiny_parts(cell), require_chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    r = run_tiny(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    names = {m["name"] for m in tiny_parts(cell)["end_to_end"]}
    assert set(r["metrics"]) == names
    assert list(r)[-1] == "checks"


def test_retention_and_stored_bytes():
    parts = tiny_parts(FROZEN)
    c = harness.Cell(parts["config"], parts["traffic"], SEED, harness.Recorder())
    c.setup()
    for _ in range(3):
        c.step()
    assert len(c.alive) == 2
    from repro.core import ReadError

    for i in range(c.saves):
        name = f"ckpt/s{i}/MANIFEST"
        if f"s{i}" in c.alive:
            c.cluster.read_object(name)
        else:
            with pytest.raises(ReadError):
                c.cluster.read_object(name)
    # Two checkpoints share one base; tiny leaves carry a few percent of
    # serialization headers.
    assert 1.0 <= c.stored_bytes_per_user_byte() < 1.06


def test_frozen_base_goes_by_reference():
    parts = tiny_parts(FROZEN)
    c = harness.Cell(parts["config"], parts["traffic"], SEED, harness.Recorder())
    c.setup()
    before = dict(c.ckpt.stats)
    c.step()
    written = c.ckpt.stats["leaves_written"] - before["leaves_written"]
    ref = c.ckpt.stats["leaves_ref_only"] - before["leaves_ref_only"]
    assert (written, ref) == (17, 12)
    assert c.change_errors == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    r = readings(tiny_parts(cell), SEED, 1)
    assert r["sound"].pop("correct") is True, r
    assert all(v == 0 for v in r["sound"].values()), r
    assert r["control"].pop("correct") is False, r
    assert any(v > 0 for v in r["control"].values()), r


@pytest.mark.parametrize("target", [1 << 12, 1 << 16, 1 << 19])
@pytest.mark.parametrize("kind", ["uniform", "low-entropy"])
def test_walked_cuts_equal_plain_cuts(target, kind):
    rng = np.random.default_rng(target)
    data = rng.integers(0, 256, 3 << 20, dtype=np.uint8)
    if kind == "low-entropy":  # long runs: forced cuts at the largest size
        data = np.repeat(data[: 3 << 14], 64)
    mask, least, largest = reference.chunk_params(target)
    plain = reference.cuts(data, mask, least, largest)
    assert len(plain) > 2
    assert reference.cuts_walk(data, mask, least, largest, piece=1 << 12) == plain
    assert reference.cuts_walk(data, mask, least, largest) == plain


def test_segments_are_whole_rows():
    assert reference.segments(1000, 30, 100) == [(0, 90), (90, 180), (180, 270), (270, 360),
                                                 (360, 450), (450, 540), (540, 630), (630, 720),
                                                 (720, 810), (810, 900), (900, 990), (990, 1000)]
    assert reference.segments(64, 128, 100) == [(0, 64)]


# ------------------------------------------- leaves over several segments
SEGMENT = 640 << 10


@pytest.fixture
def multi_segment(monkeypatch):
    """The frozen cell at tiny size with the program's segment length and
    wave row cap cut down, and the cell's stated segment length with them,
    so that its largest leaves span three segments and a save runs
    several waves."""
    harness.program()
    from repro.kernels import ops

    monkeypatch.setattr(ops, "segment_bytes", lambda spec: SEGMENT)
    monkeypatch.setattr(ops, "wave_row_cap", lambda spec: 8)
    parts = tiny_parts(FROZEN)
    parts["config"]["store"]["device_segment_bytes"] = SEGMENT
    return parts


def test_reference_matches_program_on_every_chunk(multi_segment):
    from repro.checkpoint import DedupCheckpointer
    from repro.core import DedupCluster
    from repro.kernels import ops

    specs = harness.tree_mod.leaf_specs(multi_segment["config"], multi_segment["traffic"])
    tree = harness.tree_mod.build(specs, SEED)
    spec = DedupCheckpointer(DedupCluster.create(1)).spec
    names = sorted(tree)
    fps = dict(zip(names, ops.leaf_fingerprints([tree[n] for n in names], spec)))
    assert len(ops.plan_waves([tree[n] for n in names], spec)) > 3
    largest = reference.chunk_params(spec.target_bytes)[2]
    spanning = 0
    for name in names:
        leaf = tree[name]
        host = np.asarray(leaf).reshape(-1)
        unit = (leaf.shape[-1] if leaf.ndim >= 2 else 1) * host.dtype.itemsize
        segs = reference.segments(host.nbytes, unit, SEGMENT)
        spanning += len(segs) > 1
        data = host.view(np.uint8)
        want = [reference.fingerprint(data[a + s:b + s].tobytes(), largest)
                for s, e in segs for a, b in reference.segment_chunks(data[s:e], spec.target_bytes)]
        assert fps[name] == b"".join(want), name
    assert spanning >= 2


def test_multi_segment_cell_runs_correct(multi_segment):
    r = run_tiny(FROZEN, parts=multi_segment)
    assert r["correct"], r["checks"]


def _first_segment_only(monkeypatch):
    """A fingerprint stage that fingerprints only each leaf's first
    segment."""
    from repro.kernels import ops

    real = ops.plan_waves

    def first(leaves, spec):
        waves = [[s for s in w if s[1] == 0] for w in real(leaves, spec)]
        return [w for w in waves if w]

    monkeypatch.setattr(ops, "plan_waves", first)


def _constant_later_waves(monkeypatch):
    """A fingerprint stage that answers every wave after a save's first with
    constant fingerprints."""
    import jax.numpy as jnp
    from repro.kernels import ops

    real, seen = ops._wave_impl, {"n": 0}
    first = ops.plan_waves

    def counting(leaves, spec):
        seen["n"] = 0
        return first(leaves, spec)

    def constant(segs, **kw):
        out = real(segs, **kw)
        seen["n"] += 1
        return out if seen["n"] == 1 else [(jnp.zeros_like(f), n) for f, n in out]

    monkeypatch.setattr(ops, "plan_waves", counting)
    monkeypatch.setattr(ops, "_wave_impl", constant)


def _stale_later_segments(monkeypatch):
    """A fingerprint stage that answers a wave with an earlier wave's
    fingerprints wherever their segments have the same lengths."""
    from repro.kernels import ops

    real, cache = ops._wave_impl, {}

    def stale(segs, **kw):
        return cache.setdefault(tuple(s.shape for s in segs), real(segs, **kw))

    monkeypatch.setattr(ops, "_wave_impl", stale)


def _flip_after_first_segment(monkeypatch):
    """An answer altered where it is produced, after a leaf's first
    segment: one bit of the last fingerprint of each leaf that spans more
    than one segment."""
    from repro.checkpoint import dedup_ckpt

    ops = dedup_ckpt.kops
    real = ops.leaf_fingerprints

    def flipped(leaves, spec):
        out = real(leaves, spec)
        for i, leaf in enumerate(leaves):
            if sum(len(w) for w in ops.plan_waves([leaf], spec)) > 1:
                out[i] = out[i][:-16] + bytes([out[i][-16] ^ 1]) + out[i][-15:]
        return out

    monkeypatch.setattr(ops, "leaf_fingerprints", flipped)


@pytest.mark.parametrize("fault", [_first_segment_only, _constant_later_waves,
                                   _stale_later_segments, _flip_after_first_segment],
                         ids=lambda f: f.__name__)
def test_faults_after_the_first_segment_are_caught(multi_segment, fault, monkeypatch):
    fault(monkeypatch)
    r = run_tiny(FROZEN, parts=multi_segment)
    assert not r["correct"], r["checks"]
    assert r["checks"]["fp_mismatched_chunks"]["value"] + \
        r["checks"]["fp_chunk_count_errors"]["value"] > 0, r["checks"]


# ---------------------------------------------------------------- faults
def _flip_fps(monkeypatch):
    """An answer altered where it is produced: one bit of every leaf's
    device fingerprints."""
    from repro.checkpoint import dedup_ckpt

    real = dedup_ckpt.kops.leaf_fingerprints

    def flipped(leaves, spec):
        return [bytes([fp[0] ^ 1]) + fp[1:] if fp else fp for fp in real(leaves, spec)]

    monkeypatch.setattr(dedup_ckpt.kops, "leaf_fingerprints", flipped)


def _stale_fps(monkeypatch):
    """A step that returns its state unchanged: the fingerprint stage
    answers every save with the first save's fingerprints."""
    from repro.checkpoint import dedup_ckpt

    real, first = dedup_ckpt.kops.leaf_fingerprints, {}

    def stale(leaves, spec):
        out = real(leaves, spec)
        return first.setdefault(len(leaves), out)

    monkeypatch.setattr(dedup_ckpt.kops, "leaf_fingerprints", stale)


def _half_writes(monkeypatch):
    """Half of the batch left out: the cluster write drops every other
    leaf of a save and keeps the manifest."""
    from repro.core import DedupCluster

    real = DedupCluster.write_objects

    def half(self, items):
        return real(self, items[:-1][::2] + items[-1:])

    monkeypatch.setattr(DedupCluster, "write_objects", half)


def _half_restore(monkeypatch):
    """Half of the batch left out: a restore places every other leaf."""
    from repro.checkpoint import dedup_ckpt

    real = dedup_ckpt.DedupCheckpointer.restore

    def half(self, name, like=None):
        out = real(self, name)
        out = {k: v for i, (k, v) in enumerate(sorted(out.items())) if i % 2 == 0}
        if like is None:
            return out
        import jax

        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        keys = ["/".join(str(p) for p in path) for path, _ in flat]
        missing = [k for k in keys if k not in out]
        if missing:
            raise dedup_ckpt.ReadError(f"missing leaf {missing[0]}")
        return jax.tree_util.tree_unflatten(treedef, [out[k] for k in keys])

    monkeypatch.setattr(dedup_ckpt.DedupCheckpointer, "restore", half)


def _flip_restored(monkeypatch):
    """An answer altered where it is produced: one element of each leaf a
    restore deserializes."""
    import jax.numpy as jnp
    from repro.checkpoint import dedup_ckpt

    real = dedup_ckpt._deserialize_leaf

    def flipped(data):
        x = real(data)
        return x.reshape(-1).at[0].set(x.reshape(-1)[0] + jnp.ones((), x.dtype)).reshape(x.shape)

    monkeypatch.setattr(dedup_ckpt, "_deserialize_leaf", flipped)


FAULTS = [_flip_fps, _stale_fps, _half_writes, _half_restore, _flip_restored]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_faults_are_caught(cell, fault, monkeypatch):
    harness.program()
    fault(monkeypatch)
    r = run_tiny(cell)
    assert not r["correct"], r["checks"]
