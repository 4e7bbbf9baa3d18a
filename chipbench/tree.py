"""The device pytree of a cell, made from the seed, and the traffic's update.

A configuration file lists its leaves (name, shape, dtype, mean, std); a
traffic mix may add LoRA adapters beside named leaves. Every leaf is made on
the device in one jitted call, in the dtype it is served in.
"""

from __future__ import annotations

import fnmatch
import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_specs(config: dict, traffic: dict) -> list[dict]:
    """The configuration's leaves plus the traffic's adapters, in order.

    An adapter of rank r beside a stacked (..., d_in, d_out) weight is the
    pair ``<name>.lora_a`` (..., d_in, r) and ``<name>.lora_b`` (..., r, d_out).
    """
    specs = [dict(s) for s in config["leaves"]]
    by_name = {s["name"]: s for s in specs}
    ad = traffic.get("adapters")
    if ad:
        r = ad["rank"]
        for t in ad["targets"]:
            *lead, d_in, d_out = by_name[t]["shape"]
            dt = by_name[t]["dtype"]
            specs.append({"name": f"{t}.lora_a", "shape": [*lead, d_in, r], "dtype": dt,
                          "mean": 0.0, "std": ad["std"]})
            specs.append({"name": f"{t}.lora_b", "shape": [*lead, r, d_out], "dtype": dt,
                          "mean": 0.0, "std": ad["std"]})
    return specs


def tree_bytes(specs: list[dict]) -> int:
    return sum(int(np.prod(s["shape"])) * np.dtype(jnp.dtype(s["dtype"])).itemsize for s in specs)


def build(specs: list[dict], seed: int) -> dict[str, jax.Array]:
    """Every leaf from the seed, on the device, in one jitted call."""
    sig = tuple((s["name"], tuple(s["shape"]), s["dtype"], float(s["mean"]), float(s["std"]))
                for s in specs)
    return _build(root_key(seed), sig=sig)


@functools.partial(jax.jit, static_argnames=("sig",))
def _build(key, *, sig):
    out = {}
    for i, (name, shape, dtype, mean, std) in enumerate(sig):
        dt = jnp.dtype(dtype)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, dt)
        out[name] = (x * jnp.asarray(std, dt) + jnp.asarray(mean, dt)).astype(dt)
    return out


def bits(x):
    """Same-width unsigned view, so equality is bitwise (NaN-safe)."""
    if x.dtype == jnp.bool_ or jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        return x
    return jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


def updated_names(specs: list[dict], patterns: list[str]) -> tuple[str, ...]:
    names = [s["name"] for s in specs]
    return tuple(n for n in names if any(fnmatch.fnmatchcase(n, p) for p in patterns))


def compile_update(tree: dict, names: tuple[str, ...], rel_std: float):
    """The traffic's training update, compiled ahead of the window.

    Each named leaf becomes ``w * (1 + rel_std * noise)`` in its own dtype,
    seeded by (key, step); the others are passed through. Also returns, per
    leaf, whether any of its bits changed: the truth the change detection
    is checked against.
    """

    def step(tree, key, step_no):
        k = jax.random.fold_in(key, step_no)
        new, changed = {}, {}
        for i, (name, w) in enumerate(sorted(tree.items())):
            if name in names:
                noise = jax.random.normal(jax.random.fold_in(k, i), w.shape, w.dtype)
                new[name] = (w * (1 + jnp.asarray(rel_std, w.dtype) * noise)).astype(w.dtype)
                changed[name] = jnp.any(bits(new[name]) != bits(w))
            else:
                new[name] = w
                changed[name] = jnp.zeros((), jnp.bool_)
        return new, changed

    key = jax.random.PRNGKey(0)
    return jax.jit(step).lower(tree, key, jnp.int32(0)).compile()


@jax.jit
def leaves_equal(a: dict, b: dict) -> dict:
    """Per leaf, whether two trees are bit-identical (on the device)."""
    return {k: jnp.array_equal(bits(a[k]), bits(b[k])) for k in a}
