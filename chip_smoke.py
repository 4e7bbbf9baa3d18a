#!/usr/bin/env python3
"""Bring-up smoke run of the dedup checkpoint store on one TPU chip.

    python chip_smoke.py            # from the repository root, one TPU chip

One process, no children. It drives the normal entry points
(``DedupCluster.create``, ``DedupCheckpointer.save/restore``) over the
qwen2.5-32b parameter pytree at full widths with the depth cut to 2 layers:

1. kernels: on a 32 MiB random sample, the Pallas fingerprint, window-hash
   and fused cut+fingerprint results equal the ``kernels/ref.py`` oracles,
   the host chunker, and ``chunk_cdc_scalar`` on the sample's first 4 MiB
   (CDC is causal, so a prefix's cuts are the sample's cuts below it);
2. save 1 writes every leaf; its restore is bit-identical to the tree;
3. half of the leaves change on the device, as an optimizer step would;
   save 2 writes those in full and books the others as ref-only;
4. save 2 restores bit-identically, and again after a storage node crashes.

Launch counters must equal the planned number of device waves per save.
Earlier lines are JSON bring-up observations; the last line is the result.
Any failure raises and exits non-zero without it. Exits 2 without a TPU.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import resource
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen2.5-32b"
N_LAYERS = 2
SEED = 0
SAMPLE_BYTES = 32 << 20
SCALAR_BYTES = 4 << 20


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def bits(x):
    """Same-width unsigned view, so equality is bitwise (NaN-safe)."""
    if x.dtype == jnp.bool_ or jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        return x
    return jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


@jax.jit
def _equal_bits(a, b):
    return jnp.array_equal(bits(a), bits(b))


def same_bits(a, b) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype and bool(_equal_bits(a, b))


def host_max_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class Phases:
    """Wall seconds per phase, blocked on the device before the clock
    stops, and the host's peak RSS so far (which phase raised it)."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        self.seconds[name] = time.perf_counter() - t0
        emit(phase=name, seconds=self.seconds[name], host_max_rss_bytes=host_max_rss_bytes())
        return out


def check_kernels(spec) -> None:
    from repro.core.chunking import (
        chunk_cdc, chunk_cdc_scalar, window_hashes,
    )
    from repro.kernels import ops, ref
    from repro.kernels.cdc import cdc_hashes_pallas
    from repro.kernels.fingerprint import fingerprint_chunks_pallas

    host = np.random.default_rng(SEED).integers(0, 256, SAMPLE_BYTES, dtype=np.uint8)
    data = jnp.asarray(host)

    words = ops.tensor_to_u32(data).reshape(64, -1)
    assert same_bits(fingerprint_chunks_pallas(words), ref.fingerprint_chunks(words))

    tvals = ops.gear_values(data)
    hashes = cdc_hashes_pallas(tvals)
    assert same_bits(hashes, ref.cdc_hashes(tvals))
    assert np.array_equal(np.asarray(hashes), window_hashes(host.tobytes()))

    # The default route on this chip must be the Pallas one.
    assert ops._on_tpu()
    lowered = ops._cut_and_fp_impl.lower(
        (data,), **spec.kernel_kwargs(), use_pallas=ops._on_tpu(),
        interpret=False, block_len=ops.CUT_BLOCK_LEN,
    )
    assert lowered.as_text().count("tpu_custom_call") >= 2
    (cut, n_cuts, fps, n_chunks), = ops.cdc_cut_and_fingerprint_many([data], spec=spec)
    (rcut, rn_cuts, rfps, rn_chunks), = ops.cdc_cut_and_fingerprint_many(
        [data], spec=spec, use_pallas=False
    )
    assert int(n_cuts) == int(rn_cuts) and int(n_chunks) == int(rn_chunks)
    assert same_bits(cut, rcut) and same_bits(fps, rfps)

    chunking = spec.to_chunking()
    ends = np.cumsum([len(c) for c in chunk_cdc(host.tobytes(), chunking)]) - 1
    cuts = np.asarray(cut)[: int(n_cuts)]
    assert int(n_chunks) == len(ends) and np.array_equal(cuts, ends[: len(cuts)])
    head = [len(c) for c in chunk_cdc_scalar(host[:SCALAR_BYTES].tobytes(), chunking)]
    head_cuts = np.cumsum(head[:-1]) - 1
    assert len(head_cuts) > 0 and np.array_equal(cuts[cuts < SCALAR_BYTES - 1][: len(head_cuts)], head_cuts)

    # Fused fingerprints against rows built on the host from the chunks.
    row_words, width = ops.fp_row_words(chunking.max_size)
    starts = np.concatenate([[0], ends[:-1] + 1])
    rows = np.zeros((len(ends), width), np.uint32)
    for r, (s, e) in enumerate(zip(starts, ends)):
        chunk = host[s : e + 1].tobytes()
        rows[r, :row_words] = np.frombuffer(chunk + bytes(4 * row_words - len(chunk)), "<u4")
        rows[r, row_words] = len(chunk)
    assert same_bits(np.asarray(fps)[: len(ends)], np.asarray(ref.fingerprint_chunks(jnp.asarray(rows))))
    emit(kernels="bit-identical", sample_bytes=SAMPLE_BYTES, chunks=len(ends),
         scalar_prefix_bytes=SCALAR_BYTES, scalar_cuts=len(head_cuts))


def leaves_of(tree) -> list[tuple[str, jax.Array]]:
    from repro.checkpoint.dedup_ckpt import _leaf_paths

    return _leaf_paths(tree)


def check_restore(ckpt, name, tree) -> None:
    got = jax.tree_util.tree_leaves(ckpt.restore(name, like=tree))
    for i, (key, want) in enumerate(leaves_of(tree)):
        assert same_bits(got[i], want), f"{name}: leaf {key} differs after restore"
        got[i] = None   # leave the device as soon as checked


def optimizer_step(tree, keys: set[str]):
    """Perturb the named leaves on the device, like one update."""

    @jax.jit
    def step(w, k):
        upd = w.astype(jnp.float32) + 1e-2 * jax.random.normal(k, w.shape, jnp.float32)
        return upd.astype(w.dtype)

    flat, treedef = jax.tree_util.tree_flatten(tree)
    rng = jax.random.PRNGKey(SEED + 1)
    out = []
    for (key, _), leaf in zip(leaves_of(tree), flat):
        rng, k = jax.random.split(rng)
        out.append(step(leaf, k) if key in keys else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.checkpoint import DedupCheckpointer
    from repro.compile_cache import use_compile_cache
    from repro.configs import get_config
    from repro.core import DedupCluster
    from repro.kernels import ops
    from repro.models import build_model

    emit(compile_cache=use_compile_cache())
    compile_s = [0.0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compile_s.__setitem__(0, compile_s[0] + secs)
        if ev.endswith("backend_compile_duration") else None
    )
    emit(device_kind=dev.device_kind, platform=dev.platform, count=len(jax.devices()))
    phases = Phases()

    cluster = DedupCluster.create(4, replicas=2)
    ckpt = DedupCheckpointer(cluster)
    phases.run("kernel_check", check_kernels, ckpt.spec)

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    emit(reduced={"n_layers": [full.n_layers, N_LAYERS]}, arch=ARCH,
         widths={"d_model": cfg.d_model, "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                 "head_dim": cfg.head_dim, "d_ff": cfg.d_ff, "vocab": cfg.vocab})
    tree = phases.run("init", jax.jit(build_model(cfg).init), jax.random.PRNGKey(SEED))
    named = leaves_of(tree)
    tree_bytes = sum(leaf.nbytes for _, leaf in named)
    emit(params=sum(leaf.size for _, leaf in named), leaves=len(named), tree_bytes=tree_bytes,
         bytes_in_use=dev.memory_stats()["bytes_in_use"])
    waves = len(ops.plan_waves([leaf for _, leaf in named], ckpt.spec))

    def delta(before):
        return {k: ckpt.stats[k] - before[k] for k in before}

    before = dict(ckpt.stats)
    phases.run("save_1", ckpt.save, "s1", tree)
    d = delta(before)
    assert d["leaves_written"] == len(named) and d["leaves_ref_only"] == 0, d
    assert d["cdc_launches"] == d["fp_launches"] == waves, (d, waves)
    phases.run("restore_1", check_restore, ckpt, "s1", tree)

    changed = {key for i, (key, _) in enumerate(named) if i % 2 == 0}
    tree = phases.run("optimizer_step", optimizer_step, tree, changed)
    assert [key for (key, new), (_, old) in zip(leaves_of(tree), named)
            if not same_bits(new, old)] == [key for key, _ in named if key in changed]
    del named   # the pre-step leaves leave the device
    before = dict(ckpt.stats)
    manifest = phases.run("save_2", ckpt.save, "s2", tree)
    d = delta(before)
    assert d["leaves_ref_only"] == len(manifest["leaves"]) - len(changed), d
    assert d["leaves_written"] == len(changed), d
    assert {e["key"] for e in manifest["leaves"] if not e["ref"]} == changed
    assert d["cdc_launches"] == d["fp_launches"] == waves, (d, waves)
    phases.run("restore_2", check_restore, ckpt, "s2", tree)

    crashed = sorted(cluster.nodes)[0]
    cluster.crash_node(crashed)
    phases.run("restore_2_after_crash", check_restore, ckpt, "s2", tree)

    mem = dev.memory_stats()
    emit(crashed_node=crashed, waves_per_save=waves, changed_leaves=len(changed),
         changed_bytes=sum(leaf.nbytes for key, leaf in leaves_of(tree) if key in changed),
         space_savings=cluster.space_savings(), bytes_in_use=mem["bytes_in_use"],
         peak_bytes_in_use=mem["peak_bytes_in_use"], compile_seconds=compile_s[0],
         host_max_rss_bytes=host_max_rss_bytes(),
         phase_seconds=phases.seconds)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
