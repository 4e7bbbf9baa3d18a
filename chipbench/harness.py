"""Runs one cell of ``BENCHMARK.json`` through the normal checkpoint path.

A cell names a configuration (``configs/<name>.json``: the leaves of the
device pytree, the store's layout and its guarantee) and a traffic mix
(``traffic/<name>.json``: the set-up, the update each step makes before
its save, the retention). Per-layer metrics are readers in ``metrics/<name>.py``. All
three are found by the names in ``BENCHMARK.json``, so a new cell, mix or
metric is a new file and a new entry, not an edit.

The window calls ``DedupCheckpointer.save`` and ``delete`` on a
``DedupCluster``, and the check after it ``restore``; the benchmark adds no
option to the program. It times the
calls into each layer by wrapping the instance's methods, and marks them
with ``jax.profiler.TraceAnnotation`` spans for the traced run.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import resource
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from chipbench import reference
from chipbench import trace as trace_mod
from chipbench import tree as tree_mod

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
STEP_SPAN = "step"
SPANS = (STEP_SPAN, "save.write", "save.waves", "save.retire")


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# ------------------------------------------------------------------ lookup
def load_bench(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(bench: dict, name: str) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg["file"]).read_text()),
        "traffic": json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program():
    """The system under test: the dedup cluster and its checkpointer."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.checkpoint import DedupCheckpointer
    from repro.core import DedupCluster
    from repro.core.chunking import ChunkingSpec

    return DedupCluster, ChunkingSpec, DedupCheckpointer


def ckpt_key(name: str) -> str:
    """The checkpointer's key of a top-level leaf of a dict tree."""
    return f"['{name}']"


# --------------------------------------------------------------- recording
class Recorder:
    """Host spans (seconds per call) and counters, kept while ``on``."""

    def __init__(self):
        self.on = False
        self.spans: dict[str, list[float]] = collections.defaultdict(list)
        self.counters = collections.Counter()

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if self.on:
            self.spans[name].append(time.perf_counter() - t0)


def instrument(ckpt, rec: Recorder) -> dict:
    """Wrap the instance's methods with spans; returns a dict that holds the
    device fingerprints of the latest save (key -> bytes)."""
    seen = {"fps": {}}
    fps_of, save = ckpt._batch_device_fps, ckpt.save

    def batch_device_fps(leaves):
        with rec.span("save.waves"):
            out = fps_of(leaves)
        seen["fps"] = out
        if rec.on:
            rec.counters["wave_bytes"] += sum(x.nbytes for k, x in leaves if k in out)
            rec.counters["chunks"] += sum(len(v) // 16 for v in out.values())
            rec.counters["leaves_waved"] += len(out)
        return out

    def timed_save(name, tree):
        with rec.span("save.write"):
            return save(name, tree)

    ckpt._batch_device_fps = batch_device_fps
    ckpt.save = timed_save
    return seen


# ------------------------------------------------------------------ the cell
class Cell:
    """The store, the device tree and the traffic's steps."""

    def __init__(self, config: dict, traffic: dict, seed: int, rec: Recorder):
        DedupCluster, ChunkingSpec, DedupCheckpointer = program()
        self.traffic, self.seed, self.rec = traffic, seed, rec
        self.store = store = config["store"]
        self.specs = tree_mod.leaf_specs(config, traffic)
        self.user_bytes = tree_mod.tree_bytes(self.specs)
        self.tree = jax.block_until_ready(tree_mod.build(self.specs, seed))
        self.cluster = DedupCluster.create(
            store["nodes"], replicas=store["replicas"],
            chunking=ChunkingSpec("fixed", store["host_chunk_bytes"]))
        self.ckpt = DedupCheckpointer(self.cluster)
        spec = self.ckpt.spec
        if (spec.kind, spec.target_bytes) != ("cdc", store["device_chunk_bytes"]):
            raise ValueError(f"checkpointer device spec {spec} is not the configuration's")
        self.fps = instrument(self.ckpt, rec)
        self.alive: list[str] = []
        self.saves = 0
        self.key = jax.random.fold_in(tree_mod.root_key(seed), 1)
        names = tree_mod.updated_names(self.specs, traffic["update"]["leaves"])
        self.update = tree_mod.compile_update(self.tree, names, traffic["update"]["rel_std"])
        self.update_s: list[float] = []
        self.change_errors = 0
        self.step_failed = 0

    def save_step(self, update: bool) -> None:
        with self.rec.span(STEP_SPAN):
            if update:
                t0 = time.perf_counter()
                self.tree, changed = jax.block_until_ready(
                    self.update(self.tree, self.key, np.int32(self.saves)))
                self.update_s.append(time.perf_counter() - t0)
            name = f"s{self.saves}"
            manifest = self.ckpt.save(name, self.tree)
            self.alive.append(name)
            with self.rec.span("save.retire"):
                self.retire()
        first = self.saves == 0
        self.saves += 1
        truth = {ckpt_key(k): first or bool(v)
                 for k, v in (jax.device_get(changed).items() if update else
                              ((k, True) for k in self.tree))}
        wrong = sum(truth[e["key"]] == e["ref"] for e in manifest["leaves"])
        wrong += len(truth) - len(manifest["leaves"])
        self.change_errors += wrong
        self.step_failed += wrong > 0

    def retire(self) -> None:
        """Delete the oldest checkpoints past ``keep``, then drain the GC:
        one scan marks the freed chunks, and a sweep after the aging
        threshold removes them."""
        while len(self.alive) > self.traffic["keep"]:
            self.ckpt.delete(self.alive.pop(0))
        self.cluster.run_gc()
        self.cluster.tick(self.traffic["gc_ticks"])
        self.cluster.run_gc()

    def setup(self) -> None:
        """The traffic's set-up: a first full save, then the steps it asks
        for."""
        self.save_step(update=False)
        for _ in range(self.traffic.get("setup_steps", 0)):
            self.save_step(update=True)

    def step(self) -> None:
        self.save_step(update=True)

    def stored_bytes_per_user_byte(self) -> float:
        return self.cluster.physical_bytes_stored() / (len(self.alive) * self.user_bytes)

    # -- what decides ``correct``
    def checks(self, lower: dict | None = None) -> dict:
        """Each number compared, with its limit. ``lower`` maps a leaf's
        dtype to a lower precision: the control, which stands in for the
        program's output a copy of the tree rounded to it."""
        out = {"change_errors": self.change_errors}
        out["fp_mismatched_chunks"], out["fp_chunk_count_errors"] = self.fp_mismatches(lower)
        out["restore_mismatched_leaves"] = self.restore_newest(lower)
        return {k: {"value": v, "limit": 0} for k, v in out.items()}

    def fp_mismatches(self, lower: dict | None) -> tuple[int, int]:
        """The newest save's device fingerprints against the reference, leaf
        by leaf: (chunks whose fingerprint differs, leaves whose chunk count
        differs). Every segment of every leaf is chunked by the reference,
        so each chunk's place in the leaf's fingerprints is known; compared
        are each segment's first and last chunk (its edges; the leaf's last
        segment ends in the leaf's tail) and ``fp_sample_per_segment``
        more, drawn from the seed."""
        store = self.store
        target, seg_max = store["device_chunk_bytes"], store["device_segment_bytes"]
        extra = self.traffic["check"]["fp_sample_per_segment"]
        largest = reference.chunk_params(target)[2]
        rng = np.random.default_rng(self.seed)
        bad = count_bad = compared = 0
        with ThreadPoolExecutor(check_threads()) as pool:
            for name, leaf in sorted(self.tree.items()):
                if leaf.size == 0:
                    continue
                host = np.asarray(jax.device_get(leaf)).reshape(-1)
                unit = (leaf.shape[-1] if leaf.ndim >= 2 else 1) * leaf.dtype.itemsize
                segs = reference.segments(host.nbytes, unit, seg_max)
                want, picks = _chunks(pool, host.view(np.uint8), segs, target), []
                for first, last in _segment_spans(want):
                    inner = np.arange(first + 1, last)
                    picks += [first, last] if last > first else [first]
                    picks += rng.choice(inner, min(extra, inner.size), replace=False).tolist()
                want_fps = _fingerprints(pool, host.view(np.uint8), want, picks, largest)
                if lower:  # the control: the reference on the rounded leaf
                    low = np.asarray(jax.device_get(_round(leaf, lower))).reshape(-1).view(np.uint8)
                    got_chunks = _chunks(pool, low, segs, target)
                    mine = [i for i in picks if i < len(got_chunks)]
                    got = dict(zip(mine, _fingerprints(pool, low, got_chunks, mine, largest)))
                    count = len(got_chunks)
                else:
                    fps = self.fps["fps"].get(ckpt_key(name), b"")
                    got = {i: fps[16 * i : 16 * i + 16] for i in picks}
                    count = len(fps) // 16
                count_bad += count != len(want)
                bad += sum(got.get(i) != w for i, w in zip(picks, want_fps))
                compared += len(picks)
        observe(fp_chunks_compared=compared)
        return bad, count_bad

    def restore_newest(self, lower: dict | None) -> int:
        """Leaves of the newest checkpoint that do not restore bit-identical
        to the device tree, with one node down (chosen from the seed)."""
        nodes = sorted(self.cluster.nodes)
        down = nodes[(self.seed // 7) % len(nodes)]
        self.cluster.crash_node(down)
        try:
            got = self.ckpt.restore(self.alive[-1])
        except Exception as e:  # a restore that fails is a wrong answer
            print(json.dumps({"restore_error": repr(e)}), file=sys.stderr)
            return len(self.tree)
        finally:
            self.cluster.restart_node(down)
        bad = 0
        for name, want in self.tree.items():
            leaf = got.pop(ckpt_key(name), None)
            if leaf is not None and lower:
                leaf = _round(leaf, lower)
            bad += leaf is None or not bool(tree_mod.leaves_equal({0: leaf}, {0: want})[0])
        return bad


def check_threads() -> int:
    """Threads of the reference after the window. Its cut walk runs many
    small numpy calls; past four threads they mostly wait on each other."""
    return max(1, min(4, (os.cpu_count() or 2) - 1))


def _chunks(pool, data: np.ndarray, segs: list, target: int) -> list:
    """(segment index, start, end) of every chunk of a leaf's bytes, in
    order, by the reference; each segment is chunked on its own."""
    per = pool.map(lambda se: reference.segment_chunks(data[se[0]:se[1]], target), segs)
    return [(j, a + segs[j][0], b + segs[j][0]) for j, cs in enumerate(per) for a, b in cs]


def _segment_spans(chunks: list) -> list[tuple[int, int]]:
    """(first, last) chunk index of each segment."""
    spans: dict[int, list[int]] = {}
    for i, (j, _, _) in enumerate(chunks):
        spans.setdefault(j, [i, i])[1] = i
    return [tuple(v) for v in spans.values()]


def _fingerprints(pool, data: np.ndarray, chunks: list, picks: list, largest: int) -> list:
    return list(pool.map(
        lambda i: reference.fingerprint(data[chunks[i][1]:chunks[i][2]].tobytes(), largest),
        picks))


def _round(x: jax.Array, lower: dict) -> jax.Array:
    """``x`` rounded to the lower precision of its dtype and back, on the
    device."""
    lo = lower.get(str(x.dtype))
    return x if lo is None else x.astype(jax.numpy.dtype(lo)).astype(x.dtype)


# The control: each dtype in the nearest precision below it.
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


# --------------------------------------------------------------------- run
def check_device(chips: int) -> jax.Device:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[0]


def use_compile_cache() -> str:
    program()
    from repro.compile_cache import use_compile_cache as use

    path = use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run(name: str, seed: int, seconds: float, trace: bool, t0: float, *,
        parts: dict | None = None, require_chip: bool = True,
        trace_dir: str | None = None) -> dict:
    """One run of a cell: set-up, the window, the check. Returns the result
    line; observations go to standard error as JSON lines."""
    parts = parts or cell_parts(load_bench(), name)
    cell_entry, traffic = parts["cell"], parts["traffic"]
    dev = check_device(cell_entry["chips"]) if require_chip else jax.devices()[0]
    peaks = trace_mod.load_peaks(dev.device_kind) if require_chip else None
    observe(compile_cache=use_compile_cache(), device_kind=dev.device_kind)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs) if ev.endswith("backend_compile_duration") else None)

    rec = Recorder()
    cell = Cell(parts["config"], traffic, seed, rec)
    observe(phase="built", seconds=time.perf_counter() - t0, user_bytes=cell.user_bytes,
            leaves=len(cell.tree))
    cell.setup()
    observe(phase="setup", seconds=time.perf_counter() - t0, compiles=len(compiles),
            compile_s=sum(compiles), host_max_rss_bytes=host_rss())

    log_dir = None
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
    n_compiles = len(compiles)
    load = HostLoad()
    rec.on = True
    start = time.perf_counter()
    steps = 0
    while True:
        cell.step()
        steps += 1
        if time.perf_counter() - start >= seconds:
            break
    window = time.perf_counter() - start
    rec.on = False
    if trace:
        jax.profiler.stop_trace()
    setup_s = start - t0
    observe(phase="window", steps=steps, window_s=window, compiles_in_window=len(compiles) - n_compiles,
            update_s=cell.update_s[-steps:] if cell.update_s else [],
            spans={k: v for k, v in rec.spans.items()}, host=load.stop())

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes(dev)}
    metrics = {}
    if not trace:
        values = {"setup_s": setup_s, "save_s": window / steps,
                  "stored_bytes_per_user_byte": cell.stored_bytes_per_user_byte()}
        for m in parts["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    breakdown = None
    if trace:
        reduced = trace_mod.reduce(trace_mod.load_events(trace_mod.find_xplane(log_dir)),
                                   STEP_SPAN, SPANS)
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        breakdown = trace_mod.breakdown(reduced)
        ctx = {"spans": rec.spans, "counters": rec.counters, "trace": reduced,
               "peaks": peaks, "steps": steps}
        for m in parts["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = cell.checks()
    correct = decide(checks)
    result = {"correct": correct, "attempted": steps, "failed": min(cell.step_failed, steps),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    observe(host_max_rss_bytes=host_rss(), finished_s=time.perf_counter() - t0)
    return result


class HostLoad:
    """What the host did while the window ran, for standard error: the
    process's CPU seconds and the seconds in Python's garbage collector."""

    def __init__(self):
        self.gc_s, self.gc_n, self._t = 0.0, collections.Counter(), None
        gc.callbacks.append(self._gc)
        self.start = resource.getrusage(resource.RUSAGE_SELF)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_s += time.perf_counter() - self._t
            self.gc_n[info["generation"]] += 1

    def stop(self) -> dict:
        gc.callbacks.remove(self._gc)
        end = resource.getrusage(resource.RUSAGE_SELF)
        return {"proc_user_s": end.ru_utime - self.start.ru_utime,
                "proc_sys_s": end.ru_stime - self.start.ru_stime,
                "gc_s": self.gc_s, "gc_collections": dict(self.gc_n)}


def decide(checks: dict) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def _profile_options():
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 2
    return po


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


def host_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def observe(**kv) -> None:
    print(json.dumps(kv, default=str), file=sys.stderr, flush=True)


def print_result(result: dict) -> None:
    """The checks as the last lines of standard error, and the result as the
    last line of standard output."""
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
