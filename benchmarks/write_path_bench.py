"""Write-path benchmark: vectorized CDC, batch fingerprinting, and the
serial-vs-batched write transaction. Emits ``BENCH_write_path.json`` (repo
root by default) to anchor the perf trajectory of the host write path.

Numbers on the seed (pre-vectorization): host CDC ~0.11 MB/s — the scalar
reference is re-measured here on a small sample for an honest speedup ratio.

Usage:
    PYTHONPATH=src python benchmarks/write_path_bench.py [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import (
    ChunkingSpec,
    DedupCluster,
    RepairDaemon,
    WriteError,
    fingerprint_many,
    partition,
    reliable,
)
from repro.core.chunking import chunk_cdc, chunk_cdc_scalar, chunk_object

sys.path.insert(0, str(Path(__file__).resolve().parent))
from simtime import modeled_time_clusterwide, per_edge_maxima  # noqa: E402

MB = 1024 * 1024


def _best(fn, reps: int = 3):
    """Best-of-reps wall time after one warmup; returns (seconds, last result)."""
    r = fn()  # warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn()
        best = min(best, time.perf_counter() - t0)
    return best, r


def bench_cdc(buf_bytes: int, scalar_bytes: int) -> dict:
    rng = np.random.default_rng(7)
    big = rng.bytes(buf_bytes)
    spec = ChunkingSpec("cdc", 512 * 1024)
    t_vec, _ = _best(lambda: list(chunk_cdc(big, spec)))
    # scalar oracle on a small sample with a small target so it does real
    # per-byte work (a 512K target skips min_size=128K of every chunk)
    small = big[:scalar_bytes]
    small_spec = ChunkingSpec("cdc", 16 * 1024)
    t_scalar, _ = _best(lambda: list(chunk_cdc_scalar(small, small_spec)), reps=1)
    t_vec_small, _ = _best(lambda: list(chunk_cdc(small, small_spec)))
    return {
        "buf_mib": buf_bytes / MB,
        "vectorized_mb_s": buf_bytes / t_vec / 1e6,
        "scalar_mb_s": scalar_bytes / t_scalar / 1e6,
        "vectorized_mb_s_same_input": scalar_bytes / t_vec_small / 1e6,
        "speedup_same_input": t_scalar / t_vec_small,
        "n_chunks": len(list(chunk_cdc(big, spec))),
    }


def bench_fingerprint(buf_bytes: int) -> dict:
    rng = np.random.default_rng(8)
    data = rng.bytes(buf_bytes)
    chunks = chunk_object(data, ChunkingSpec("fixed", 512 * 1024))
    t, _ = _best(lambda: fingerprint_many(chunks))
    return {
        "buf_mib": buf_bytes / MB,
        "n_chunks": len(chunks),
        "mb_s": buf_bytes / t / 1e6,
        "chunks_per_s": len(chunks) / t,
    }


def bench_device_cdc(buf_bytes: int) -> dict:
    """Fused device CDC + fingerprint pipeline: one CDC launch + one
    fingerprint launch for a whole wave of tensor byte streams (the
    checkpoint save shape). ``fused_mb_s`` is wall-clock (NOT gated);
    ``n_chunks``, ``boundary_checksum`` (u32 sum of all inclusive cut
    offsets) and the launches-per-save counters are exact functions of the
    seeded wave + ChunkingSpec — any drift means the kernel's cut selection
    or the fusion contract changed, and the bench gate holds them at
    tolerance 0."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointConfig, DedupCheckpointer
    from repro.core.chunking import cdc_mask
    from repro.kernels import ops as kops

    rng = np.random.default_rng(15)
    # uneven wave: one dominant leaf + small stragglers, like a real pytree
    weights = [8, 4, 2, 1, 1]
    sizes = [max(1, buf_bytes * w // sum(weights)) for w in weights]
    streams = [
        jnp.asarray(rng.integers(0, 256, size=s, dtype=np.uint8)) for s in sizes
    ]
    target, mn, mx = 8 * 1024, 4 * 1024, 16 * 1024

    def run():
        res = kops.cdc_cut_and_fingerprint_many(
            streams, mask=cdc_mask(target), min_size=mn, max_size=mx
        )
        jax.block_until_ready([r[2] for r in res])
        return res

    t, res = _best(run)
    n_chunks = 0
    checksum = np.uint64(0)
    for cutpos, n_cuts, _, nc in res:
        n_chunks += int(nc)
        cp = np.asarray(jax.device_get(cutpos))[: int(n_cuts)].astype(np.uint64)
        checksum = (checksum + cp.sum(dtype=np.uint64)) % np.uint64(1 << 32)
    # launches-per-save through the checkpointer (the contract the fusion
    # exists for: whole pytree, one launch pair)
    cluster = DedupCluster.create(4, chunking=ChunkingSpec("fixed", 64 * 1024))
    ckpt = DedupCheckpointer(
        cluster, CheckpointConfig(fp_chunk_bytes=target, device_cdc=True)
    )
    ckpt.save("bench", {f"leaf{i}": s for i, s in enumerate(streams)})
    return {
        "buf_mib": buf_bytes / MB,
        "n_streams": len(streams),
        "fused_mb_s": buf_bytes / t / 1e6,
        "n_chunks": n_chunks,
        "boundary_checksum": int(checksum),
        "cdc_launches_per_save": ckpt.stats["cdc_launches"],
        "fp_launches_per_save": ckpt.stats["fp_launches"],
    }


def bench_write_path(n_objects: int, obj_bytes: int) -> dict:
    rng = np.random.default_rng(9)
    # ~50% duplicate content so the dedup path is exercised
    pool = [rng.bytes(obj_bytes) for _ in range(max(2, n_objects // 2))]
    items = [(f"o{i}", pool[i % len(pool)]) for i in range(n_objects)]
    spec = ChunkingSpec("cdc", 8 * 1024)

    def serial():
        # chunk-granular messaging (the pre-batching transaction shape)
        c = DedupCluster.create(8, chunking=spec, batch_unicasts=False)
        for name, data in items:
            c.write_object(name, data)
        return c

    def batched():
        # per-object node batching (the PR 1 message shape)
        c = DedupCluster.create(8, chunking=spec, coalesce_batches=False)
        c.write_objects(list(items))
        return c

    def coalesced():
        # cross-object coalescing: one ChunkOpBatch per node for the whole
        # batch; intra-batch duplicate chunks ride ref-only ops
        c = DedupCluster.create(8, chunking=spec)
        c.write_objects(list(items))
        return c

    # Interleaved best-of-4: the three variants differ by ~10% wall time on
    # top of identical chunking+fingerprint work, so round-robin the reps to
    # expose each variant to the same scheduler noise and take per-variant
    # minima.
    variants = {"serial": serial, "batched": batched, "coalesced": coalesced}
    best = {k: float("inf") for k in variants}
    result = {}
    for k, fn in variants.items():
        result[k] = fn()  # warmup
    for _ in range(4):
        for k, fn in variants.items():
            t0 = time.perf_counter()
            result[k] = fn()
            best[k] = min(best[k], time.perf_counter() - t0)
    t_serial, cs = best["serial"], result["serial"]
    t_batched, cb = best["batched"], result["batched"]
    t_coalesced, cc = best["coalesced"], result["coalesced"]
    for other in (cb, cc):
        assert cs.dedup_ratio() == other.dedup_ratio(), "dedup ratio must match serial"
        assert cs.unique_bytes_stored() == other.unique_bytes_stored()
    snap_s, snap_b, snap_c = (
        cs.stats.snapshot(), cb.stats.snapshot(), cc.stats.snapshot()
    )
    assert snap_c["control_msgs"] < snap_b["control_msgs"]
    assert snap_c["net_bytes"] <= snap_b["net_bytes"]
    return {
        "n_objects": n_objects,
        "obj_kib": obj_bytes / 1024,
        "serial_objects_s": n_objects / t_serial,
        "batched_objects_s": n_objects / t_batched,
        "coalesced_objects_s": n_objects / t_coalesced,
        "speedup": t_serial / t_batched,
        "coalesced_speedup": t_serial / t_coalesced,
        "dedup_ratio": cc.dedup_ratio(),
        "control_msgs_serial": snap_s["control_msgs"],
        "control_msgs_batched": snap_b["control_msgs"],
        "control_msgs_coalesced": snap_c["control_msgs"],
        "chunk_msgs_serial": cs.transport.msgs_by_type.get("chunk_op_batch", 0),
        "chunk_msgs_batched": cb.transport.msgs_by_type.get("chunk_op_batch", 0),
        "chunk_msgs_coalesced": cc.transport.msgs_by_type.get("chunk_op_batch", 0),
        "net_bytes_batched": snap_b["net_bytes"],
        "net_bytes_coalesced": snap_c["net_bytes"],
        # at-least-once accounting: every delivery acked; reliable run -> 0 retries
        "ack_bytes_coalesced": snap_c["ack_bytes"],
        "retransmits_coalesced": snap_c["retransmits"],
    }


def bench_write_cache(n_objects: int, obj_bytes: int) -> dict:
    """Presence-cache probe elision at ~50% duplicate content, cache on vs
    off. Two batches through one session: batch 2 rewrites batch 1's
    content pool under new names, so every batch-2 chunk is a cross-batch
    repeat only the presence cache can turn into a presence-asserted
    ref-only op. Both runs stream in bounded waves, so intra-batch repeats
    are ref-only via the wave-local first-writer set either way — the
    lookup/elision delta isolates the cache's contribution. Every column
    except the throughput one is a deterministic function of the workload
    and the wire model — the bench gate holds them at tolerance 0."""
    rng = np.random.default_rng(9)
    pool = [rng.bytes(obj_bytes) for _ in range(max(2, n_objects // 2))]
    batch1 = [(f"a{i}", pool[i % len(pool)]) for i in range(n_objects)]
    batch2 = [(f"b{i}", pool[i % len(pool)]) for i in range(n_objects)]
    spec = ChunkingSpec("cdc", 8 * 1024)
    wave = max(4 * obj_bytes, 64 * 1024)

    def run(presence):
        c = DedupCluster.create(8, chunking=spec)
        s = c.client(presence_cache=presence, wave_bytes=wave)
        s.put_many(list(batch1))
        s.put_many(list(batch2))
        return c

    c_off = run(0)  # warmup is also the cache-off reference
    t_on, c_on = _best(lambda: run(4096))
    off, on = c_off.stats.snapshot(), c_on.stats.snapshot()
    assert c_off.dedup_ratio() == c_on.dedup_ratio(), (
        "presence elision must not change what is stored"
    )
    assert on["probe_elisions"] > 0
    assert on["lookup_unicasts"] < off["lookup_unicasts"], (
        "cache-on must carry strictly fewer CIT probes"
    )
    assert (
        on["lookup_unicasts"] + on["probe_elisions"] == off["lookup_unicasts"]
    ), "every elision accounts for exactly one skipped probe"
    assert on["presence_fallbacks"] == 0, "no invalidations here -> no fallbacks"
    return {
        "n_objects": 2 * n_objects,
        "obj_kib": obj_bytes / 1024,
        "cache_on_objects_s": 2 * n_objects / t_on,  # wall clock; NOT gated
        "dedup_ratio": c_on.dedup_ratio(),
        "lookups_cache_off": off["lookup_unicasts"],
        "lookups_cache_on": on["lookup_unicasts"],
        "probe_elisions": on["probe_elisions"],
        "elision_rate": on["probe_elisions"] / off["lookup_unicasts"],
        "cache_hits": on["cache_hits"],
        "cache_evictions": on["cache_evictions"],
        "control_msgs_cache_off": off["control_msgs"],
        "control_msgs_cache_on": on["control_msgs"],
        "net_bytes_cache_off": off["net_bytes"],
        "net_bytes_cache_on": on["net_bytes"],
        "presence_fallbacks": on["presence_fallbacks"],
        "peak_dirty_bytes_cache_on": on["peak_dirty_bytes"],
        "wave_bytes": wave,
    }


def bench_read_path(n_objects: int, obj_bytes: int) -> dict:
    """Coalesced batch restore vs the serial read oracle on the
    write-cache bench's ~50%-dup two-batch workload (batch b re-stores
    batch a's content pool under new names, so the restore batch shares
    chunks across objects). The batched engine must return byte-identical
    data with >= 3x fewer read messages while fetching every distinct
    chunk of the batch exactly once: its read payload equals the
    cluster's unique stored bytes, where the serial oracle pays for every
    recipe reference (the fetch_elisions delta). The fragmentation
    columns measure how wide dedup scatters one logical object across
    nodes — the restore-cost baseline ROADMAP item 5's placement work is
    judged against. Every column except the two *_objects_s wall-clock
    ones is a deterministic function of the workload and the wire model —
    the bench gate holds them at tolerance 0."""
    rng = np.random.default_rng(9)
    pool = [rng.bytes(obj_bytes) for _ in range(max(2, n_objects // 2))]
    items = [(f"a{i}", pool[i % len(pool)]) for i in range(n_objects)]
    items += [(f"b{i}", pool[i % len(pool)]) for i in range(n_objects)]
    names = [n for n, _ in items]
    spec = ChunkingSpec("cdc", 8 * 1024)

    def populate():
        c = DedupCluster.create(8, chunking=spec)
        c.write_objects(list(items))
        c.tick(2)
        return c

    def read(c, batched):
        c.batch_reads = batched
        frag: list = []
        m0, n0, a0 = c.stats.control_msgs, c.stats.net_bytes, c.stats.ack_bytes
        t0 = time.perf_counter()
        if batched:
            data = c.read_objects(names, frag_out=frag)
        else:
            data = [c.read_object(n) for n in names]
        wall = time.perf_counter() - t0
        msgs = c.stats.control_msgs - m0
        # net_bytes carries payload + acks (control headers are wire_bytes),
        # and read requests are payload-free, so this is the response payload
        payload = (c.stats.net_bytes - n0) - (c.stats.ack_bytes - a0)
        return data, msgs, c.stats.net_bytes - n0, payload, wall, frag

    cs, cb = populate(), populate()
    oracle, msgs_serial, net_serial, payload_serial, t_serial, _ = read(cs, False)
    got, msgs_batched, net_batched, payload_batched, t_batched, frag = read(cb, True)
    assert got == oracle == [d for _, d in items], (
        "batched restore must be byte-identical to the serial oracle"
    )
    assert msgs_serial >= 3 * msgs_batched, "read messages must drop >= 3x"
    assert cb.stats.fetch_elisions > 0
    assert payload_batched == cb.unique_bytes_stored(), (
        "each distinct chunk of the batch must travel exactly once"
    )
    assert payload_serial == sum(len(d) for _, d in items), (
        "the serial oracle fetches every recipe reference"
    )
    return {
        "n_objects": 2 * n_objects,
        "obj_kib": obj_bytes / 1024,
        "serial_objects_s": 2 * n_objects / t_serial,    # wall; NOT gated
        "batched_objects_s": 2 * n_objects / t_batched,  # wall; NOT gated
        "read_msgs_serial": msgs_serial,
        "read_msgs_batched": msgs_batched,
        "msg_reduction": msgs_serial / msgs_batched,
        "read_net_bytes_serial": net_serial,
        "read_net_bytes_batched": net_batched,
        "read_payload_serial": payload_serial,
        "read_payload_batched": payload_batched,
        "read_batches": cb.stats.read_batches,
        "read_fallback_rounds": cb.stats.read_fallback_rounds,
        "fetch_elisions": cb.stats.fetch_elisions,
        # restore fragmentation: how wide one logical object scatters
        "frag_chunks_total": sum(f["chunks"] for f in frag),
        "frag_nodes_touched_total": sum(f["nodes"] for f in frag),
        "frag_nodes_touched_max": max(f["nodes"] for f in frag),
        "frag_spread_max": max(f["max_chunks_one_node"] for f in frag),
        # per-edge modeled time of each cluster's full run (same writes,
        # different read shape): the delta is the read path's modeled win
        "modeled_time_per_edge_serial_s": modeled_time_clusterwide(
            cs, link_model="per_edge"
        ),
        "modeled_time_per_edge_batched_s": modeled_time_clusterwide(
            cb, link_model="per_edge"
        ),
    }


def bench_recovery(n_objects: int, obj_bytes: int) -> dict:
    """Recovery-round cost model on a fixed split-brain schedule: writes
    across an open partition, heal, client retries, then the full
    digest-repair + refcount-audit + GC round. Every column except the
    wall-clock one is a deterministic function of the workload and the
    wire model — the bench gate holds them at tolerance 0."""
    rng = np.random.default_rng(11)
    spec = ChunkingSpec("fixed", 2048)
    c = DedupCluster.create(6, replicas=2, chunking=spec)
    c.write_objects([(f"base{i}", rng.bytes(obj_bytes)) for i in range(n_objects)])
    c.tick(3)
    c.transport.policy = partition(
        ("oss0", "oss1", "oss2"), ("oss3", "oss4", "oss5")
    )
    items = [(f"w{i}", rng.bytes(obj_bytes)) for i in range(n_objects)]
    failed = []
    for name, data in items:
        try:
            c.write_object(name, data)
        except WriteError:
            failed.append((name, data))
    c.transport.policy = reliable()
    for name, data in failed:
        c.write_object(name, data)
    net_before, msgs_before = c.stats.net_bytes, c.stats.control_msgs
    t0 = time.perf_counter()
    report = c.recover()
    wall = time.perf_counter() - t0
    return {
        "n_objects": n_objects,
        "obj_kib": obj_bytes / 1024,
        "writes_failed_during_partition": len(failed),
        "digest_msgs": c.transport.msgs_by_type.get("digest_request", 0),
        "repair_msgs": c.transport.msgs_by_type.get("repair_chunk", 0),
        "audit_msgs": report.audit_msgs,
        "omap_repaired": report.omap_repaired,
        "chunks_repaired": report.chunks_repaired,
        "cit_repaired": report.cit_repaired,
        "repair_bytes": report.repair_bytes,
        "refs_over": report.refs_over,
        "refs_under": report.refs_under,
        "flags_flipped": report.flags_flipped,
        "gc_removed": report.gc_removed,
        "recovery_net_bytes": c.stats.net_bytes - net_before,
        "recovery_msgs": c.stats.control_msgs - msgs_before,
        # both link models pinned: the legacy uniform n-way split and the
        # per-edge straggler-NIC bottleneck (the default)
        "modeled_time_uniform_s": modeled_time_clusterwide(c, link_model="uniform"),
        "modeled_time_per_edge_s": modeled_time_clusterwide(c, link_model="per_edge"),
        "recovery_wall_s": wall,  # noisy; NOT gated
    }


def bench_always_on(n_objects: int, obj_bytes: int) -> dict:
    """Always-on recovery cost model: tombstone wire traffic and the
    incremental epoch-scoped digest scope. A cold ``RepairDaemon`` round
    digests every placement group; after a small steady-state mutation
    window (one rewrite + one delete) the next round re-digests strictly
    fewer groups — the claim the asserts pin and the gated columns
    quantify. A third round past the GC horizon reaps the delete's
    tombstone. Every column is a deterministic function of the workload
    and the wire model — the bench gate holds them at tolerance 0."""
    rng = np.random.default_rng(13)
    spec = ChunkingSpec("fixed", 2048)
    c = DedupCluster.create(6, replicas=2, chunking=spec)
    c.write_objects([(f"o{i}", rng.bytes(obj_bytes)) for i in range(n_objects)])
    c.tick(3)
    daemon = RepairDaemon(c)
    r_cold = daemon.step()  # cold start: unknown past, every group digested
    # steady state: a small mutation window, then an incremental round
    c.write_object("o1", rng.bytes(obj_bytes))
    c.delete_object("o2")
    c.tick(1)
    net_before, msgs_before = c.stats.net_bytes, c.stats.control_msgs
    r_incr = daemon.step()
    incr_net = c.stats.net_bytes - net_before
    incr_msgs = c.stats.control_msgs - msgs_before
    assert r_incr.groups_skipped > 0, "clean groups must be skipped"
    assert r_incr.groups_digested < r_cold.groups_digested, (
        "an incremental round must re-digest strictly fewer groups"
    )
    # age the tombstone past the GC horizon; the next round reaps it
    c.tick(31)
    r_reap = daemon.step()
    assert r_reap.tombstones_reaped > 0, "aged full-acked tombstone must reap"
    return {
        "n_objects": n_objects,
        "obj_kib": obj_bytes / 1024,
        "cold_groups_digested": r_cold.groups_digested,
        "incr_groups_digested": r_incr.groups_digested,
        "incr_groups_skipped": r_incr.groups_skipped,
        "incr_round_net_bytes": incr_net,
        "incr_round_msgs": incr_msgs,
        "tombstone_commit_msgs": c.transport.msgs_by_type.get("omap_delete", 0),
        "tombstone_reap_msgs": c.transport.msgs_by_type.get("tombstone_reap", 0),
        "tombstones_reaped": r_reap.tombstones_reaped,
        "audit_deferred": (
            r_cold.audit_deferred + r_incr.audit_deferred + r_reap.audit_deferred
        ),
    }


def bench_multi_tenant(n_clients: int, n_objects: int, ops_per_client: int) -> dict:
    """Multi-tenant scheduled workload (core/workload.py over the
    discrete-event Scheduler): N concurrent client sessions, Zipf names
    and sizes, mixed put/get/delete, bursty seeded arrivals. Every column
    is a deterministic function of the spec seed — the bench gate holds
    them at tolerance 0. The asserts pin the interleaving claims the
    refactor exists for: >= 2 sessions with sent-but-uncommitted waves at
    one tick, and wave k+1 chunking overlapping wave k in flight.

    The seen-window sizing study rides along: the same spec at 2/4/8
    clients, recording peak window occupancy per in-flight depth. These
    measured margins replace the chaos suites' old fixed 25%-of-capacity
    assertion (tests/conftest.py keeps only the zero-eviction claim)."""
    from repro.core import Scheduler, WorkloadSpec, run_workload

    spec_of = lambda nc: WorkloadSpec(  # noqa: E731
        clients=nc, objects=n_objects, ops_per_client=ops_per_client,
        seed=5, bulk_first=2, wave_bytes=8192, presence_cache=32,
    )

    # sizing study first (small sweeps), headline 8-client run last so the
    # contention columns come from the full-width cluster
    window_capacity = 1024
    sweep: dict[int, int] = {}
    for nc in (2, 4):
        cs = DedupCluster.create(4, replicas=2, chunking=ChunkingSpec("fixed", 2048))
        run_workload(cs, spec_of(nc))
        assert cs.stats.seen_evictions == 0, "sizing sweep must not evict"
        sweep[nc] = cs.stats.seen_high_water

    c = DedupCluster.create(4, replicas=2, chunking=ChunkingSpec("fixed", 2048))
    sched = Scheduler(c, seed=5)
    t0 = time.perf_counter()
    rep = run_workload(c, spec_of(n_clients), scheduler=sched)
    wall = time.perf_counter() - t0
    assert c.stats.seen_evictions == 0, "sizing sweep must not evict"
    sweep[n_clients] = c.stats.seen_high_water
    assert rep["max_in_flight_sessions"] >= 2, (
        "scheduler must interleave >= 2 sessions"
    )
    assert c.stats.waves_overlapped >= 1, "wave pipelining must overlap"
    edges = per_edge_maxima(c)
    totals = rep["totals"]
    return {
        "clients": n_clients,
        "objects": n_objects,
        "ops_per_client": ops_per_client,
        "ops_total": totals["ops"],
        "puts_ok": totals["puts_ok"],
        "gets_ok": totals["gets_ok"],
        "deletes_ok": totals["deletes_ok"],
        "not_found": totals["not_found"],
        "failures": totals["failures"],
        "bytes_written": totals["bytes_written"],
        "latency_p50_ticks": totals["latency_p50_ticks"],
        "latency_p99_ticks": totals["latency_p99_ticks"],
        "elapsed_ticks": rep["elapsed_ticks"],
        "scheduler_steps": rep["scheduler_steps"],
        "max_in_flight_sessions": rep["max_in_flight_sessions"],
        "waves_overlapped": c.stats.waves_overlapped,
        "writes_superseded": c.stats.writes_superseded,
        "probe_elisions": c.stats.probe_elisions,
        "cache_hits": c.stats.cache_hits,
        "net_bytes": c.stats.net_bytes,
        "control_msgs": c.stats.control_msgs,
        "busiest_edge": edges["busiest_edge"],
        "busiest_edge_payload": edges["busiest_edge_payload"],
        "node_ingress_max": edges["node_ingress_max"],
        "node_egress_max": edges["node_egress_max"],
        "seen_window_capacity": window_capacity,
        "seen_high_water_c2": sweep[2],
        "seen_high_water_c4": sweep[4],
        "seen_high_water_c8": sweep[n_clients],
        # measured margin (percent of capacity) at full client width — the
        # number the old fixed 25% assertion guessed at
        "seen_margin_pct_c8": sweep[n_clients] * 100 // window_capacity,
        "modeled_time_uniform_s": modeled_time_clusterwide(c, link_model="uniform"),
        "modeled_time_per_edge_s": modeled_time_clusterwide(c, link_model="per_edge"),
        "workload_wall_s": wall,  # noisy; NOT gated
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small inputs (CI smoke)")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    if args.quick:
        cdc_bytes, scalar_bytes = 1 * MB, 64 * 1024
        fp_bytes = 4 * MB
        dev_cdc_bytes = 256 * 1024
        n_objects, obj_bytes = 40, 32 * 1024
        rec_objects, rec_bytes = 16, 8 * 1024
        mt_objects, mt_ops = 24, 8
    else:
        cdc_bytes, scalar_bytes = 8 * MB, 256 * 1024
        fp_bytes = 32 * MB
        dev_cdc_bytes = 2 * MB
        n_objects, obj_bytes = 200, 64 * 1024
        rec_objects, rec_bytes = 48, 16 * 1024
        mt_objects, mt_ops = 64, 20

    report = {
        "quick": args.quick,
        "cdc": bench_cdc(cdc_bytes, scalar_bytes),
        "device_cdc": bench_device_cdc(dev_cdc_bytes),
        "fingerprint": bench_fingerprint(fp_bytes),
        "write_path": bench_write_path(n_objects, obj_bytes),
        "write_cache": bench_write_cache(n_objects, obj_bytes),
        "read_path": bench_read_path(n_objects, obj_bytes),
        "recovery": bench_recovery(rec_objects, rec_bytes),
        "always_on": bench_always_on(rec_objects, rec_bytes),
        "multi_tenant": bench_multi_tenant(8, mt_objects, mt_ops),
    }
    out = args.out or Path(__file__).resolve().parent.parent / "BENCH_write_path.json"
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report, indent=2))
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
