# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import ckpt_bench, kernel_bench, paper_figs
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    rows: list[str] = ["name,us_per_call,derived"]
    sections = [
        ("Fig 4(a) bandwidth vs chunk size", paper_figs.fig4a_chunk_size),
        ("Fig 4(b) bandwidth vs dedup ratio", paper_figs.fig4b_dedup_ratio),
        ("Fig 5(a) scalability vs client threads", paper_figs.fig5a_scalability),
        ("Fig 5(b) consistency variants", paper_figs.fig5b_consistency_variants),
        ("Table 2 space savings vs #disks", paper_figs.table2_space_savings),
        ("Beyond-paper: fingerprint-first network", paper_figs.fp_first_network),
        ("Kernel microbench", kernel_bench.run),
        ("Dedup checkpointing", ckpt_bench.run),
    ]
    for title, fn in sections:
        print(f"# --- {title} ---", file=sys.stderr, flush=True)
        fn(rows)
    print("\n".join(rows))


if __name__ == "__main__":
    main()
