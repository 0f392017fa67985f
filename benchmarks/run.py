"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--quick`` shrinks sweeps.
``--smoke`` is the CI perf-trajectory job: only the real-data-plane
sections (paged_engine + gateway) on tiny configs. ``--json-out FILE``
additionally serializes every row (plus per-section timings) as JSON —
the artifact the smoke workflow uploads so a perf history accumulates.
Exits non-zero when any section raised (its ERROR row still lands in
the CSV and the JSON artifact).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

SMOKE_SECTIONS = ("paged_engine", "gateway")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config real-data-plane sections only "
                         "(implies --quick)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json-out", default=None,
                    help="write all rows + timings to this JSON file")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import autotune_bench, common, eviction_index, \
        gateway_bench, kernel_bench, paged_engine_bench, roofline_report
    from benchmarks import serving_suite as S

    benches = {
        "frontier": S.frontier,                      # Fig. 10
        "tail_latency": S.tail_latency,              # Fig. 11 (left)
        "continuity": S.continuity,                  # Fig. 11 (right)
        "arrivals": S.arrivals,                      # Fig. 12
        "bargein_sensitivity": S.bargein_sensitivity,  # Fig. 13
        "ablation": S.ablation,                      # Fig. 14
        "rtf_pacing": S.rtf_pacing,                  # Fig. 15
        "token_waste": S.token_waste,                # Fig. 16 (left)
        "reload_path": S.reload_path,                # Fig. 16 (right)
        "kv_residency": S.kv_residency,              # Fig. 17
        "continuity_timeline": S.continuity_timeline,  # Fig. 18
        "eviction_index": eviction_index.run,        # Table 1
        "paged_engine": paged_engine_bench.run,      # real data plane
        "gateway": gateway_bench.run,                # DESIGN.md §4
        "kernels": kernel_bench.run,
        "autotune": autotune_bench.run,              # DESIGN.md §16
        "roofline": roofline_report.run,             # §Roofline
    }
    only = set(args.only.split(",")) if args.only else None
    quick = args.quick or args.smoke
    if args.smoke:
        only = set(SMOKE_SECTIONS) & (only or set(SMOKE_SECTIONS))
        if not only:
            ap.error(f"--only selects no smoke sections "
                     f"(smoke runs {','.join(SMOKE_SECTIONS)})")
    print("name,us_per_call,derived")
    t0 = time.time()
    timings = {}
    errors = 0
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t1 = time.time()
        try:
            fn(quick=quick)
        except Exception as e:                       # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stderr)
            # through row() so the crash also lands in the JSON artifact
            common.row(f"{name}/ERROR", 0.0, type(e).__name__)
            errors += 1
        timings[name] = time.time() - t1
        print(f"# {name} done in {timings[name]:.1f}s", flush=True)
    total = time.time() - t0
    print(f"# total {total:.1f}s")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"rows": common.ROWS, "section_s": timings,
                       "total_s": total,
                       "mode": ("smoke" if args.smoke
                                else "quick" if args.quick else "full")},
                      f, indent=1)
        print(f"# wrote {len(common.ROWS)} rows to {args.json_out}",
              flush=True)
    if errors:
        # a green run with ERROR rows would silently stop measuring
        print(f"# {errors} section(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
