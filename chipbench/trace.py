"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time over the traced window, device
time per operation name, and the device's idle gaps attributed to the
benchmark's host spans."""

from __future__ import annotations

import bisect
import collections
import glob
import json
import pathlib

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _profile(path: str):
    """A ``.xplane.pb``, or one compressed with gzip (``.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load_events(path: str) -> dict:
    """Device op and program events per chip and the host spans, as (name,
    start_ns, end_ns), from one ``.xplane.pb``. A device op's name is its
    HLO instruction as the trace gives it, e.g. ``%fusion.3 = u32[...]
    fusion(...)``; a Pallas kernel is a ``custom-call`` named after the
    jitted function that holds it. A program's name is its module, e.g.
    ``jit__wave_impl(1020...)``."""
    pd = _profile(path)
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE) and plane.name[len(DEVICE_PLANE):].isdigit():
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    (devices if line.name == OPS_LINE else modules)[plane.name] = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events
                )
    return {"devices": devices, "modules": modules, "host": host}


def _module_of(modules: list) -> callable:
    """Name (without its ``(id)``) of the program running at a time."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [a for _, a, _ in mods]

    def at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= mods[i][2]:
            return "(no program)"
        return mods[i][0].split("(", 1)[0]

    return at


def reduce(events: dict, window_span: str, spans: tuple[str, ...]) -> dict:
    """Busy and window seconds, device seconds per op name and per program
    (the union of the ops that ran inside it: ops nest, so their sum
    counts some time twice), and idle seconds per host span.

    The window runs from the first start to the last end of the host spans
    named ``window_span`` (one per timed step). Busy is the union of the
    device op intervals inside it, averaged over the chips. An idle gap is
    charged to the innermost of ``spans`` that covers its middle, or to
    ``(no span)``.
    """
    steps = [(a, b) for n, a, b in events["host"] if n == window_span]
    if not steps:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    named = sorted(((a, b, n) for n, a, b in events["host"] if n in spans),
                   key=lambda s: (s[0], -s[1]))
    busy_ns, per_op, per_module = 0, collections.Counter(), collections.Counter()
    gaps = collections.Counter()
    chips = events["devices"]
    for chip, ops in chips.items():
        module_at = _module_of(events.get("modules", {}).get(chip, []))
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ops if b > lo and a < hi]
        by_module = collections.defaultdict(list)
        for n, a, b in inside:
            per_op[n] += (b - a) * 1e-9
            by_module[module_at(a)].append((a, b))
        for m, ivs in by_module.items():
            per_module[m] += sum(b - a for a, b in _union(ivs)) * 1e-9
        busy = _union([(a, b) for _, a, b in inside])
        busy_ns += sum(b - a for a, b in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            cover = [n for s, e, n in named if s <= mid < e]
            # Spans start in order and nest, so the last one covering the
            # middle is the innermost.
            gaps[cover[-1] if cover else "(no span)"] += (b - a) * 1e-9 / len(chips)
    n_chips = max(1, len(chips))
    return {
        "chips": len(chips),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_chips,
        "op_s": {k: v / n_chips for k, v in per_op.items()},
        "module_s": {k: v / n_chips for k, v in per_module.items()},
        "idle_s": dict(gaps),
    }


def op_seconds(reduced: dict, pattern: tuple[str, ...]) -> float:
    """Device seconds of the ops whose name starts with ``pattern[0]`` and
    contains every other part of it."""
    return sum(s for n, s in reduced["op_s"].items()
               if n.startswith(pattern[0]) and all(p in n for p in pattern[1:]))


def breakdown(reduced: dict, top: int = 10, width: int = 160) -> dict:
    """The ops that took most device time (names cut to ``width``) and the
    host spans with most idle device time."""
    ops = collections.Counter()
    for n, s in reduced["op_s"].items():
        ops[n[:width]] += s
    ops = ops.most_common(top)
    idle = sorted(reduced["idle_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in idle]}


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown kind is
    an error, never a default."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def summary(path: str, top: int = 40) -> dict:
    """Planes, lines and the longest device ops of a trace, to look at one
    by hand: ``python3 -m chipbench.trace <file.xplane.pb>``."""
    pd = _profile(path)
    planes = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            ev = collections.Counter()
            for e in line.events:
                ev[e.name] += e.duration_ns * 1e-9
            lines[line.name] = sorted(ev.items(), key=lambda kv: -kv[1])[:top]
        planes[plane.name] = lines
    return planes


if __name__ == "__main__":
    import sys

    print(json.dumps(summary(sys.argv[1]), indent=1))
