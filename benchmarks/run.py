"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. REPRO_BENCH_STEPS scales the
training-based reproductions (default 150 steps/phase)."""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    from . import (fig7_accuracy_bpp, fig9_layer_bpp, runtime_proxy,
                   serve_throughput, table1_smol_variants, table2_patterns)
    benches = [
        ("table2_patterns", table2_patterns.main),
        # explicit empty argv: the harness's own sys.argv must not leak
        # into the benchmark's argparse
        ("runtime_proxy", lambda: runtime_proxy.main([])),
        ("table1_smol_variants", table1_smol_variants.main),
        ("fig7_accuracy_bpp", fig7_accuracy_bpp.main),
        ("fig9_layer_bpp", fig9_layer_bpp.main),
        # explicit empty argv: the harness's own sys.argv must not leak
        # into the benchmark's argparse
        ("serve_throughput", lambda: serve_throughput.main([])),
    ]
    failures = 0
    for name, fn in benches:
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name},0,FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
