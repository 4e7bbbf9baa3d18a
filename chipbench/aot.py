"""Compile a cell's distinct save-wave programs for a described TPU v5e chip,
without the chip, and print compile seconds and ``memory_analysis()`` per
program (JSON lines).

    JAX_PLATFORMS=cpu python3 -m chipbench.aot --workload <cell>

What the chip's compiler refuses here costs no chip time. A compile that
passes is not a chip run.
"""

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.aot")
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, tree

    jax.config.update("jax_enable_compilation_cache", False)
    parts = harness.cell_parts(harness.load_bench(), args.workload)
    harness.program()
    from repro.checkpoint import DedupCheckpointer
    from repro.core import DedupCluster
    from repro.kernels import ops

    spec = DedupCheckpointer(DedupCluster.create(1)).spec
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    leaves = [jax.ShapeDtypeStruct(tuple(s["shape"]), jnp.dtype(s["dtype"]), sharding=chip)
              for s in sorted(tree.leaf_specs(parts["config"], parts["traffic"]),
                              key=lambda s: harness.ckpt_key(s["name"]))]
    waves = ops.plan_waves(leaves, spec)
    seen = {}
    for wave in waves:
        sig = tuple((leaves[i].shape, str(leaves[i].dtype), k) for i, _, k in wave)
        seen.setdefault(sig, wave)
    print(json.dumps({"workload": args.workload, "waves": len(waves), "distinct": len(seen)}), flush=True)
    total = 0.0
    for sig, wave in seen.items():
        segs_in = [leaves[i] for i, _, _ in wave]
        starts = [jax.ShapeDtypeStruct((), jnp.int32, sharding=chip) for _ in wave]
        sizes = tuple(k for _, _, k in wave)
        t0 = time.perf_counter()
        seg_out = jax.eval_shape(lambda ls, st: ops._segments(ls, st, sizes=sizes), segs_in, starts)
        ops._segments.lower(segs_in, starts, sizes=sizes).compile()
        flat = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip) for s in seg_out]
        c = ops._wave_impl.lower(flat, spec=spec, use_pallas=True).compile()
        secs = time.perf_counter() - t0
        total += secs
        m = c.memory_analysis()
        print(json.dumps({
            "segments": [[list(shape), dt, k] for shape, dt, k in sig],
            "segment_bytes": sum(s.size * s.dtype.itemsize for s in seg_out),
            "compile_s": secs,
            "temp_bytes": m.temp_size_in_bytes, "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "kernels": c.as_text().count("tpu_custom_call"),
        }), flush=True)
    print(json.dumps({"workload": args.workload, "compile_s_total": total}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
