"""The program's own spans (``repro.tracing``) in a chip trace, reduced by
``chipbench.trace`` the way the benchmark's spans are.

``data/small_program.xplane.pb.gz`` is ``record_trace.py`` run on a TPU v5e
chip on a program that has the spans (its output file renamed): the tiny
frozen-save cell, with the benchmark's spans and the program's on one host
plane. ``data/small.xplane.pb.gz`` predates the program's spans; its
reduction is pinned here.
"""

from __future__ import annotations

import pathlib

import pytest

from chipbench import harness, trace
from chipbench.tests.test_trace import metric_module

DATA = pathlib.Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb.gz"
PROGRAM = DATA / "small_program.xplane.pb.gz"
# ``ckpt.waves`` and the spans under it, which the device waits out.
WAVE_SPANS = ("ckpt.waves", "ckpt.wave", "ckpt.wave.dispatch", "ckpt.wave.fetch",
              "ckpt.wave.unpack")
SAVE_SPANS = WAVE_SPANS + ("ckpt.save", "ckpt.ref_write", "ckpt.serialize", "ckpt.put",
                           "ckpt.commit", "ckpt.delete", "cluster.gc")


@pytest.fixture(scope="module")
def events():
    return trace.load_events(str(PROGRAM))


@pytest.fixture(scope="module")
def reduced(events):
    return trace.reduce(events, harness.STEP_SPAN, harness.SPANS)


@pytest.fixture(scope="module")
def program(events):
    """The idle gaps charged to the program's spans instead."""
    return trace.reduce(events, harness.STEP_SPAN, SAVE_SPANS)


def window_seconds(events) -> dict[str, list[float]]:
    """Seconds of each host span inside the timed steps, per name."""
    steps = [(a, b) for n, a, b in events["host"] if n == harness.STEP_SPAN]
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    out: dict[str, list[float]] = {}
    for n, a, b in events["host"]:
        if lo <= a and b <= hi:
            out.setdefault(n, []).append((b - a) * 1e-9)
    return out


def test_old_reduction_is_unchanged():
    r = trace.reduce(trace.load_events(str(SMALL)), harness.STEP_SPAN, harness.SPANS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1.0242608770000001, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.5233861790000001, rel=1e-12)
    assert r["idle_s"] == pytest.approx({
        "step": 0.0025142659999998975, "save.waves": 0.17373473899999425,
        "save.write": 0.19950893600000003, "save.retire": 0.125116757}, rel=1e-9)
    assert r["module_s"] == pytest.approx({
        "jit_step": 0.000231985, "jit__segments": 0.000351384,
        "jit__wave_impl": 0.522700766, "jit_copy": 0.000102044}, rel=1e-9)
    assert len(r["op_s"]) == 1106


@pytest.mark.parametrize("name", SAVE_SPANS)
def test_program_span_in_every_save(events, name):
    secs = window_seconds(events)
    saves = len(secs["save.write"])
    assert saves > 1
    assert name not in harness.SPANS
    got = secs.get(name, [])
    assert got and all(s >= 0 for s in got)
    if name in ("ckpt.save", "ckpt.waves", "ckpt.put", "ckpt.commit"):
        assert len(got) == saves


def test_program_spans_nest_in_the_benchmark_spans(events):
    """``ckpt.save`` lies in ``save.write`` and ``ckpt.waves`` in
    ``save.waves``: the benchmark wraps the program's methods."""
    host = events["host"]
    for inner, outer in (("ckpt.save", "save.write"), ("ckpt.waves", "save.waves")):
        spans = [(a, b) for n, a, b in host if n == outer]
        for n, a, b in host:
            if n == inner:
                assert any(s <= a and b <= e for s, e in spans), (inner, a)


def test_waves_span_makes_up_the_benchmark_waves(events):
    """Fetch plus the rest of ``ckpt.waves`` (plan, dispatch, unpack) is
    ``ckpt.waves``; that is within 2% of the benchmark's ``save.waves``."""
    secs = window_seconds(events)
    fetch, waves = sum(secs["ckpt.wave.fetch"]), sum(secs["ckpt.waves"])
    assert 0 < fetch <= waves
    assert waves == pytest.approx(sum(secs["save.waves"]), rel=0.02)


def test_wave_gaps_within_the_benchmark_waves_idle(reduced, program):
    """Device idle under the program's wave spans is at most the idle the
    benchmark's reduction charges to ``save.waves``; both reductions
    charge the same idle in all."""
    gap = sum(program["idle_s"].get(n, 0.0) for n in WAVE_SPANS)
    assert 0 <= gap <= reduced["idle_s"]["save.waves"] * (1 + 1e-9)
    assert sum(program["idle_s"].values()) == pytest.approx(sum(reduced["idle_s"].values()),
                                                            rel=1e-9)
    assert set(program["idle_s"]) <= set(SAVE_SPANS) | {"(no span)"}


def test_kernels_found_by_their_names(reduced):
    fp = metric_module("fp_roofline.save").KERNEL
    assert trace.op_seconds(reduced, fp) > 0
    assert trace.op_seconds(reduced, ("%cdc_cuts_pallas", "custom-call(")) > 0
    assert trace.op_seconds(reduced, ("%_cut_and_fp_impl", "custom-call(")) == 0
    assert reduced["module_s"][metric_module("cdc_roofline.save").PROGRAM] > 0


def test_existing_readers_read_the_new_trace(reduced):
    ctx = {"trace": reduced, "peaks": trace.load_peaks("TPU v5 lite"), "steps": 1,
           "counters": {"wave_bytes": 5_000_000, "chunks": 40, "leaves_waved": 29}}
    for name in ("cdc_roofline.save", "fp_roofline.save", "idle.save"):
        v = metric_module(name).read(ctx)
        assert v is not None and 0 < v < 100, (name, v)
