"""Cells at a size the CPU runs in seconds: every dimension of every leaf
divided by ``shrink`` (at least 1), the store and the traffic as committed."""

from __future__ import annotations

import copy

from chipbench import harness


def cells() -> list[str]:
    return [w["name"] for w in harness.load_bench()["workloads"]]


def tiny_parts(cell: str, shrink: int = 32) -> dict:
    parts = copy.deepcopy(harness.cell_parts(harness.load_bench(), cell))
    for leaf in parts["config"]["leaves"]:
        leaf["shape"] = [max(1, d // shrink) for d in leaf["shape"]]
    return parts
