"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``record_trace.py``: the tiny frozen-save cell), and the peaks table."""

from __future__ import annotations

import pathlib

import pytest

from chipbench import harness, trace

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load_events(str(SMALL)), harness.STEP_SPAN, harness.SPANS)


def test_window_busy_and_idle(reduced):
    assert reduced["chips"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # Every idle second is charged to a span or to no span, once.
    idle = sum(reduced["idle_s"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(reduced["idle_s"]) <= set(harness.SPANS) | {"(no span)"}
    # A save's idle time lies in the waves' host work and in the write.
    assert reduced["idle_s"].get("save.write", 0) > 0


def test_kernels_and_programs_found(reduced):
    fp = metric_module("fp_roofline.save").KERNEL
    cdc = metric_module("cdc_roofline.save")
    assert trace.op_seconds(reduced, fp) > 0
    assert trace.op_seconds(reduced, ("%_cut_and_fp_impl", "custom-call(")) > 0
    assert cdc.FP_KERNEL == fp
    # The cut stage is the wave program less the fingerprint kernel, and
    # the cut kernel is part of it.
    stage = reduced["module_s"][cdc.PROGRAM] - trace.op_seconds(reduced, fp)
    assert stage > trace.op_seconds(reduced, ("%_cut_and_fp_impl", "custom-call("))
    assert sum(reduced["op_s"].values()) >= reduced["busy_s"] * (1 - 1e-9)
    # Programs partition the busy time: their unions add up to it.
    assert sum(reduced["module_s"].values()) == pytest.approx(reduced["busy_s"], rel=1e-3)


def test_roofline_readers_stay_under_the_peak(reduced):
    # Counters of about the tiny traced save's size: 29 leaves, 5 MB.
    ctx = {"trace": reduced, "peaks": trace.load_peaks("TPU v5 lite"), "step": "save",
           "counters": {"wave_bytes": 5_000_000, "chunks": 40, "leaves_waved": 29}}
    for name in ("cdc_roofline.save", "fp_roofline.save"):
        v = metric_module(name).read(ctx)
        assert v is not None and 0 < v < 100, (name, v)
    assert metric_module("cdc_roofline.save").read({**ctx, "counters": {}}) is None


def metric_module(metric: str):
    import importlib.util

    path = pathlib.Path(harness.HERE) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_breakdown_is_bounded(reduced):
    b = trace.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(k, str) and v > 0 for k, v in b["device_ops"])


def test_union_merges_overlaps():
    assert trace._union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]


def test_peaks_known_and_unknown():
    assert trace.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        trace.load_peaks("TPU v9 imaginary")


def test_harness_refuses_cpu():
    with pytest.raises(harness.NoChip):
        harness.check_device(1)
