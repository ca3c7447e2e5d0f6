"""Benchmark harness: one module per paper table/figure + integrations.

Prints ``name,us_per_call,derived`` CSV.  LIX_BENCH_N scales datasets
(default 500k keys).  LIX_BENCH_FAST=1 trims the slowest studies.
"""

import os
import sys
import time
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    fast = os.environ.get("LIX_BENCH_FAST", "0") == "1"
    from benchmarks import (
        fig4_maps, fig5_weblog, fig6_lognormal, fig7_strings, fig8_search,
        fig10_hash, fig13_bloom, naive_index, moe_dispatch, paged_kv,
        dynamic_index,
    )

    suites = [
        ("fig4_maps", fig4_maps.main),
        ("fig5_weblog", fig5_weblog.main),
        ("fig6_lognormal", fig6_lognormal.main),
        ("fig7_strings", fig7_strings.main),
        ("fig8_search", fig8_search.main),
        ("fig10_hash", fig10_hash.main),
        ("fig13_bloom", None if fast else fig13_bloom.main),
        ("naive_index", naive_index.main),
        ("moe_dispatch", moe_dispatch.main),
        ("paged_kv", paged_kv.main),
        ("dynamic_index", dynamic_index.main),
    ]
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        if fn is None:
            print(f"# {name}: skipped (LIX_BENCH_FAST)")
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {name}: done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name}: FAILED\n{traceback.format_exc()}", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
