"""Benchmark harness: one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV (stdout), one row each.

    PYTHONPATH=src python -m benchmarks.run [--only substring]
                                           [--smoke] [--json PATH]

``--smoke`` runs the fast CI subset (kernel_perf.SMOKE plus the
serving_goodput gate) — the per-PR perf-trajectory gate scripts/ci.sh
uses.  ``--json PATH`` also
writes the rows as a JSON baseline (see benchmarks/README.md for how the
fields are meant to be read).
"""
import argparse
import json
import sys
import traceback


def _derived_fields(derived: str) -> dict:
    """Parse the 'k=v;k=v' derived string into typed fields where possible."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: fused/ensemble engine benches only")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to PATH as a JSON baseline")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import kernel_perf, serving_bench

    if args.smoke:
        benches = list(kernel_perf.SMOKE) + list(serving_bench.SMOKE)
    else:
        from benchmarks import (paper_experiments, roofline_report,
                                straggler_bench)
        benches = (paper_experiments.ALL + kernel_perf.ALL
                   + straggler_bench.ALL + serving_bench.ALL
                   + roofline_report.ALL)

    print("name,us_per_call,derived")
    rows = {}
    failed = 0
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            name, us, derived = fn()
            print(f"{name},{us:.1f},{derived}", flush=True)
            # JSON rows are keyed by the python bench name so a bench that
            # flips between erroring and passing keeps a stable key across
            # runs; the reported CSV name rides along as a field.
            rows[fn.__name__] = {"name": name, "us_per_call": round(us, 1),
                                 "derived": _derived_fields(derived)}
            if "FAIL" in derived:
                failed += 1
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{fn.__name__},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            rows[fn.__name__] = {"name": None, "us_per_call": None,
                                 "derived": {"error": f"{type(e).__name__}:{e}"}}

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
