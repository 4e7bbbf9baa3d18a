"""Deduplicated, fault-tolerant distributed checkpointing.

This is the paper's technique integrated as a first-class framework feature:

* every pytree leaf is serialized, chunked, SHA-256-fingerprinted and placed
  *cluster-wide by content fingerprint* on the shared-nothing DedupCluster;
* repeated checkpoints dedup against each other (optimizer ints, frozen
  embeddings, converged tensors, replicated experts, multi-run storage);
* commit flags + GC make a crash mid-save harmless (no journal);
* restore hits the read path's consistency check, which repairs
  missing/invalid chunks from replicas — the paper §2.4 duplicate-write case.

Device-fingerprint fast path (beyond paper, uses the Pallas kernels): before
pulling a tensor to the host, fingerprint it on device and compare with the
previous save; unchanged tensors are written by *reference* (refcount-only
unicasts, no data motion). Falls back to a full write if any referenced
chunk is missing (repair), so the fast path is safe. A kernel failure
raises out of ``save``; only leaves the kernels do not take (by type, see
``_device_leaf``) skip the fast path.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DedupCluster, ReadError
from repro.core.chunking import ChunkSpec
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    prefix: str = "ckpt"
    device_fp_fastpath: bool = True
    # Consolidated chunking surface for the device-fingerprint fast path:
    # kind "cdc" + device=True runs the fused chunk+fingerprint pipeline
    # (ONE CDC launch + ONE fingerprint launch per byte-bounded save wave,
    # kops.plan_waves); kind "fixed" runs fixed-size chunking (ONE
    # fingerprint launch per wave).
    # When unset, built from the legacy fields below (accepted and mapped
    # for one release).
    chunk_spec: ChunkSpec | None = None
    # Legacy chunking spelling (.. deprecated:: prefer ``chunk_spec``):
    fp_chunk_bytes: int = 512 * 1024
    device_cdc: bool = True
    cdc_min_bytes: int = 0      # 0 -> fp_chunk_bytes // 2
    cdc_max_bytes: int = 0      # 0 -> fp_chunk_bytes * 2
    # Streaming ingest: bound the transport wave (and peak host dirty-chunk
    # bytes) for the batched leaf write — the whole checkpoint no longer
    # materializes at once; wave k is on the wire while wave k+1 chunks.
    # 0 = one wave for the whole checkpoint (the legacy shape).
    wave_bytes: int = 0
    # Fingerprint presence-cache capacity for the writing session (0 = off):
    # repeat saves elide CIT probes for chunks the session has positive
    # evidence for (see docs/write_cache.md).
    presence_cache: int = 0

    def resolved_chunk_spec(self) -> ChunkSpec:
        if self.chunk_spec is not None:
            return self.chunk_spec
        return ChunkSpec.for_checkpoint(
            self.fp_chunk_bytes,
            min_bytes=self.cdc_min_bytes,
            max_bytes=self.cdc_max_bytes,
            device=self.device_cdc,
        )


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(p) for p in path)
        out.append((key, leaf))
    return out


def _device_leaf(leaf) -> bool:
    """Whether the device fingerprint kernels take this leaf: a jax or numpy
    array of bool, integer or float dtype. Numpy leaves wider than 4 bytes
    need x64, or the device copy would round them and hide changes. Other
    leaves (Python scalars, strings, complex or object arrays) get no device
    fingerprint and are written in full on every save."""
    if isinstance(leaf, jax.Array):
        dt = leaf.dtype
    elif isinstance(leaf, (np.ndarray, np.generic)):
        dt = leaf.dtype
        if dt.itemsize > 4 and not jax.config.jax_enable_x64:
            return False
    else:
        return False
    return dt == np.bool_ or jnp.issubdtype(dt, jnp.integer) or jnp.issubdtype(dt, jnp.floating)


def _serialize_leaf(leaf) -> bytes:
    if isinstance(leaf, jax.Array):
        # A jax.Array keeps the host copy np.asarray makes for as long as it
        # lives; fetching a device copy leaves the caller's tree without one.
        leaf = jnp.array(leaf, copy=True)
    arr = np.asarray(jax.device_get(leaf))
    header = json.dumps({"dtype": arr.dtype.name, "shape": list(arr.shape)}).encode()
    # One copy of the payload: join reads the flat array's buffer in place.
    flat = np.ascontiguousarray(arr.reshape(-1)).view(np.uint8)
    return b"".join((len(header).to_bytes(4, "big"), header, flat.data))


def _deserialize_leaf(data: bytes):
    hlen = int.from_bytes(data[:4], "big")
    meta = json.loads(data[4 : 4 + hlen].decode())
    dtype = jnp.bfloat16 if meta["dtype"] == "bfloat16" else np.dtype(meta["dtype"])
    arr = np.frombuffer(data, dtype, offset=4 + hlen).reshape(meta["shape"])
    return jnp.asarray(arr)


class DedupCheckpointer:
    def __init__(self, cluster: DedupCluster, cfg: CheckpointConfig | None = None):
        self.cluster = cluster
        self.cfg = cfg or CheckpointConfig()
        self.spec = self.cfg.resolved_chunk_spec()
        # The writing session: a dedicated DedupClient when streaming waves
        # or a presence cache are configured, else the cluster's default
        # (cache-disabled) session — byte-for-byte the legacy write path.
        if self.cfg.wave_bytes or self.cfg.presence_cache:
            self.session = cluster.client(
                presence_cache=self.cfg.presence_cache,
                wave_bytes=self.cfg.wave_bytes,
            )
        else:
            self.session = None
        # leafpath -> (device fp bytes, object name last written)
        self._last_device_fps: dict[str, tuple[bytes, str]] = {}
        self.stats = {
            "leaves_written": 0,
            "leaves_ref_only": 0,
            "bytes_sent": 0,
            # kernel-launch accounting for the device fast path: asserts the
            # one-CDC-launch + one-fingerprint-launch-per-wave contract
            "cdc_launches": 0,
            "fp_launches": 0,
        }

    # ------------------------------------------------------------------ save
    def save(self, name: str, tree: Any) -> dict[str, Any]:
        leaves = _leaf_paths(tree)
        # Batched device fingerprinting: one launch pair per byte-bounded
        # wave of device leaves (vs one per leaf), then per-leaf ref-write
        # decisions.
        fp_cache = self._batch_device_fps(leaves)
        manifest = {"name": name, "leaves": []}
        full_writes: list[tuple[str, bytes]] = []
        for key, leaf in leaves:
            obj_name = f"{self.cfg.prefix}/{name}/{key}"
            if self._ref_write(key, obj_name, fp_cache.get(key)):
                manifest["leaves"].append({"key": key, "object": obj_name, "ref": True})
                self.stats["leaves_ref_only"] += 1
                continue
            data = _serialize_leaf(leaf)
            full_writes.append((obj_name, data))
            manifest["leaves"].append({"key": key, "object": obj_name, "ref": False})
        mbytes = json.dumps(manifest).encode()
        # One batched write transaction for all full leaves + the manifest,
        # riding the cross-object coalesced transport path: one ChunkOpBatch
        # unicast per storage node for the WHOLE checkpoint, and chunks
        # shared between leaves (replicated experts, tied embeddings) ship
        # their bytes once — later leaves ride ref-only ops. write_objects
        # commits items in order and raises at the first failure, so the
        # writes_ok delta counts exactly the committed leaves — including on
        # a mid-batch failure.
        # With ``wave_bytes`` set the session streams the batch in bounded
        # waves instead (chunk+fingerprint wave k+1 while wave k's batches
        # are on the wire; O(wave) host dirty bytes), and a configured
        # presence cache elides CIT probes for chunks repeated across saves.
        writer = (
            self.session.put_many
            if self.session is not None
            else self.cluster.write_objects
        )
        ok_before = self.cluster.stats.writes_ok
        try:
            writer(
                full_writes + [(f"{self.cfg.prefix}/{name}/MANIFEST", mbytes)]
            )
        finally:
            committed = min(self.cluster.stats.writes_ok - ok_before, len(full_writes))
            self.stats["leaves_written"] += committed
            self.stats["bytes_sent"] += sum(len(d) for _, d in full_writes[:committed])
        # drain async flag flips (the paper's consistency manager)
        self.cluster.tick(2)
        return manifest

    def _batch_device_fps(self, leaves: list[tuple[str, Any]]) -> dict[str, bytes]:
        """Chunk + fingerprint every device leaf on the device, in
        byte-bounded waves (``kops.plan_waves``): with a CDC spec ONE fused
        CDC launch plus ONE fingerprint launch per wave, with a fixed spec
        ONE fingerprint launch per wave. A small pytree is one wave. Returns
        leafpath -> raw fingerprint bytes. A kernel failure raises."""
        if not self.cfg.device_fp_fastpath:
            return {}
        arr = [(k, leaf) for k, leaf in leaves if _device_leaf(leaf)]
        before = kops.launch_snapshot()
        try:
            fps = kops.leaf_fingerprints([leaf for _, leaf in arr], self.spec)
        finally:
            after = kops.launch_snapshot()
            self.stats["cdc_launches"] += after["cdc"] - before["cdc"]
            self.stats["fp_launches"] += after["fingerprint"] - before["fingerprint"]
        return {k: fp for (k, _), fp in zip(arr, fps)}

    def _ref_write(self, key: str, obj_name: str, fp_bytes: bytes | None) -> bool:
        """Device-fp fast path: if the tensor is unchanged since the last
        save (per its device fingerprint), create the new object as a
        reference-only write against the previous one — refcount unicasts,
        zero data motion. Returns True on success; False for a leaf without
        a device fingerprint."""
        if fp_bytes is None:
            return False
        prev = self._last_device_fps.get(key)
        self._last_device_fps[key] = (fp_bytes, obj_name)
        if prev is None or prev[0] != fp_bytes:
            return False
        ofp = self.cluster.write_object_by_ref(obj_name, prev[1])
        return ofp is not None

    # --------------------------------------------------------------- restore
    def restore(self, name: str, like: Any | None = None) -> Any:
        mbytes = self.cluster.read_object(f"{self.cfg.prefix}/{name}/MANIFEST")
        manifest = json.loads(mbytes.decode())
        # One coalesced restore for every leaf: leaves sharing chunks (the
        # dedup win this checkpointer exists for) are fetched once per
        # batch, and each node serves its chunks in one ChunkReadBatch.
        ents = manifest["leaves"]
        blobs = self.cluster.read_objects([ent["object"] for ent in ents])
        leaves = {}
        for i, ent in enumerate(ents):
            leaves[ent["key"]] = _deserialize_leaf(blobs[i])
            blobs[i] = None     # the host copy goes once its leaf is placed
        if like is None:
            return leaves
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        out = []
        for path, leaf in flat:
            key = "/".join(str(p) for p in path)
            if key not in leaves:
                raise ReadError(f"checkpoint {name} missing leaf {key}")
            out.append(leaves[key])
        return jax.tree_util.tree_unflatten(treedef, out)

    def delete(self, name: str) -> None:
        mbytes = self.cluster.read_object(f"{self.cfg.prefix}/{name}/MANIFEST")
        manifest = json.loads(mbytes.decode())
        # ref'd objects belong to an earlier checkpoint; delete only our own
        own = {e["object"] for e in manifest["leaves"] if not e.get("ref")}
        for obj in own:
            self.cluster.delete_object(obj)
        self.cluster.delete_object(f"{self.cfg.prefix}/{name}/MANIFEST")

    def list_checkpoints(self) -> list[str]:
        names = set()
        for node in self.cluster.nodes.values():
            for name in node.shard.omap:
                if name.startswith(self.cfg.prefix + "/") and name.endswith("/MANIFEST"):
                    names.add(name.split("/")[1])
        return sorted(names)
