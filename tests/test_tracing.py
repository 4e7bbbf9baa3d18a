"""Program spans (``repro.tracing``) along the checkpoint path, and the save
counters in ``DedupCheckpointer.stats``, on a small CPU save that runs
several device waves."""

import collections
import glob
import json

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.checkpoint import CheckpointConfig, DedupCheckpointer
from repro.core import ChunkingSpec, DedupCluster
from repro.core.chunking import ChunkSpec
from repro.kernels import ops as kops

SPEC = ChunkSpec.for_checkpoint(1024)


@pytest.fixture
def small_waves(monkeypatch):
    """A wave budget of 12 fingerprint rows: the tree below runs as several
    waves."""
    _, width = kops.fp_row_words(SPEC.max_bytes)
    monkeypatch.setattr(kops, "WAVE_ROW_BYTES", 12 * width * 4)


@pytest.fixture
def recording():
    tracing.reset()
    tracing.record(True)
    try:
        yield
    finally:
        tracing.record(False)
        tracing.reset()


def _tree() -> dict:
    rng = np.random.default_rng(5)
    return {
        "big": jnp.asarray(rng.standard_normal((64, 160)), jnp.float32),
        "flat": jnp.asarray(rng.integers(0, 2**16, 9000, dtype=np.uint16)).view(jnp.bfloat16),
        "small": jnp.arange(10, dtype=jnp.int32),
        "step": 3,  # no device fingerprint: written in full every save
    }


def _ckpt() -> DedupCheckpointer:
    cluster = DedupCluster.create(3, chunking=ChunkingSpec("fixed", 16 * 1024))
    return DedupCheckpointer(cluster, CheckpointConfig(chunk_spec=SPEC))


def _device_waves(tree: dict) -> int:
    return len(kops.plan_waves([x for x in tree.values() if isinstance(x, jax.Array)], SPEC))


def _session(ckpt: DedupCheckpointer) -> dict:
    """Two saves (the second changes one leaf), the first deleted, GC run,
    the second restored."""
    tree = _tree()
    ckpt.save("s1", tree)
    tree2 = dict(tree, big=tree["big"].at[63, 5].add(1.0))
    ckpt.save("s2", tree2)
    ckpt.delete("s1")
    ckpt.cluster.run_gc()
    ckpt.restore("s2")
    return tree2


def test_recording_off_keeps_nothing(small_waves):
    tracing.reset()
    _session(_ckpt())
    assert tracing.totals() == {}
    assert tracing.records() == []


@pytest.fixture
def session_spans(small_waves, recording):
    tree = _session(_ckpt())
    return tracing.records(), _device_waves(tree)


# (span, its parent, spans per session, with w the device waves of a save)
TREE = [
    ("ckpt.save", None, lambda w: 2),
    ("ckpt.waves", "ckpt.save", lambda w: 2),
    ("ckpt.wave", "ckpt.waves", lambda w: 2 * w),
    ("ckpt.wave.dispatch", "ckpt.wave", lambda w: 2 * w),
    ("ckpt.wave.fetch", "ckpt.wave", lambda w: 2 * w),
    ("ckpt.wave.unpack", "ckpt.wave", lambda w: 2 * w),
    # s1 writes its 4 leaves in full; s2 the changed leaf and the int, and
    # the 2 unchanged arrays by reference
    ("ckpt.serialize", "ckpt.save", lambda w: 6),
    ("ckpt.ref_write", "ckpt.save", lambda w: 2),
    ("ckpt.put", "ckpt.save", lambda w: 2),
    ("ckpt.commit", "ckpt.save", lambda w: 2),
    ("ckpt.delete", None, lambda w: 1),
    ("cluster.gc", None, lambda w: 1),
    ("ckpt.restore", None, lambda w: 1),
    ("ckpt.restore.read", "ckpt.restore", lambda w: 1),
    ("ckpt.restore.place", "ckpt.restore", lambda w: 1),
]


@pytest.mark.parametrize("name, parent, count", TREE, ids=[t[0] for t in TREE])
def test_span_tree(session_spans, name, parent, count):
    recs, waves = session_spans
    assert waves > 1
    mine = [r for r in recs if r[0] == name]
    assert len(mine) == count(waves)
    assert {None if p is None else recs[p][0] for _, p, _, _ in mine} == {parent}
    assert all(a <= b for _, _, a, b in mine)


def test_every_span_is_named_in_the_tree(session_spans):
    recs, _ = session_spans
    assert {r[0] for r in recs} == {t[0] for t in TREE}


def test_wave_is_dispatch_fetch_unpack(session_spans):
    recs, _ = session_spans
    for i, (name, _, a, b) in enumerate(recs):
        if name == "ckpt.wave":
            kids = [r for r in recs if r[1] == i]
            assert [k[0] for k in kids] == ["ckpt.wave.dispatch", "ckpt.wave.fetch",
                                            "ckpt.wave.unpack"]
            assert a <= kids[0][2] and kids[-1][3] <= b


def test_self_time_is_duration_less_children(session_spans):
    recs, _ = session_spans
    want = collections.defaultdict(float)
    for i, (name, _, a, b) in enumerate(recs):
        want[name] += (b - a) - sum(d - c for _, p, c, d in recs if p == i)
    got = tracing.totals()
    assert set(got) == set(want)
    for name, t in got.items():
        assert t["self_s"] == pytest.approx(want[name], abs=1e-9)
        assert 0 <= t["self_s"] <= t["s"] + 1e-12
        assert t["n"] == sum(r[0] == name for r in recs)


def test_nested_spans_by_hand(recording):
    with tracing.span("a"):
        with tracing.span("b"):
            pass
        with tracing.span("b"):
            with tracing.span("c"):
                pass
    recs = tracing.records()
    assert [(n, p) for n, p, _, _ in recs] == [("a", None), ("b", 0), ("b", 0), ("c", 2)]
    t = tracing.totals()
    a, b = recs[0], recs[1:3]
    assert t["a"]["n"] == 1 and t["b"]["n"] == 2 and t["c"]["n"] == 1
    assert t["a"]["self_s"] == pytest.approx(
        (a[3] - a[2]) - sum(r[3] - r[2] for r in b), abs=1e-12)
    assert t["c"]["self_s"] == pytest.approx(t["c"]["s"], abs=1e-12)


def test_stats_count_the_waves(small_waves):
    """``wave_bytes``, ``chunks`` and ``leaves_waved`` count as the chip
    benchmark's wrapper of ``_batch_device_fps`` (chipbench
    ``harness.instrument``) counts from what the method returns."""
    ckpt = _ckpt()
    seen, real = collections.Counter(), ckpt._batch_device_fps

    def counting(leaves):
        out = real(leaves)
        seen["wave_bytes"] += sum(x.nbytes for k, x in leaves if k in out)
        seen["chunks"] += sum(len(v) // 16 for v in out.values())
        seen["leaves_waved"] += len(out)
        return out

    ckpt._batch_device_fps = counting
    tree = _session(ckpt)
    for key in ("wave_bytes", "chunks", "leaves_waved"):
        assert ckpt.stats[key] == seen[key] > 0, key
    assert ckpt.stats["waves"] == 2 * _device_waves(tree) == ckpt.stats["fp_launches"]
    manifest = ckpt.cluster.read_object("ckpt/s2/MANIFEST")
    refs = [e["key"] for e in json.loads(manifest)["leaves"] if e["ref"]]
    by_key = {f"['{k}']": v for k, v in tree.items()}
    assert len(refs) == 2
    assert ckpt.stats["ref_bytes"] == sum(by_key[k].nbytes for k in refs)


def test_profiler_trace_holds_program_spans(tmp_path):
    """The spans land on the host plane of a ``jax.profiler`` trace, with
    recording off."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        _session(_ckpt())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {t[0] for t in TREE} <= names
