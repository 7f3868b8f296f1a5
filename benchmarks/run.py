"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--only <name>`` selects one
module by exact name (``bench_ttft`` or the ``bench_``-less shorthand
``ttft`` — *not* substring matching, so ``ttft`` can never also pick up
a future ``bench_ttft_decode``).  ``--list`` prints the module names.
An import failure aborts immediately with the module name and a
non-zero exit (a module that cannot even import must not be reported as
a mere row failure); ``run()`` failures are collected and reported at
the end.
"""
from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    "bench_compression",
    "bench_slicing",
    "bench_layout_search",
    "bench_entropy",
    "bench_ttft",
    "bench_adaptive",
    "bench_nonreuse",
    "bench_memory",
    "bench_decode_throughput",
    "bench_kernels",
]


def selected(only: str | None) -> list:
    """Exact-name selection: ``bench_x`` or the shorthand ``x``."""
    if only is None:
        return list(MODULES)
    return [m for m in MODULES if only in (m, m.removeprefix("bench_"))]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run exactly one module: bench_ttft or ttft")
    ap.add_argument("--list", action="store_true",
                    help="print available module names and exit")
    args = ap.parse_args()
    if args.list:
        for m in MODULES:
            print(m)
        return
    mods = selected(args.only)
    if not mods:
        raise SystemExit(
            f"--only {args.only!r} matches no module; --list shows "
            f"valid names (exact, with or without the bench_ prefix)")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod_name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        except Exception as e:  # noqa: BLE001
            # an unimportable module is a broken harness, not a data
            # point: name it and stop before any run() is attempted
            raise SystemExit(
                f"benchmarks.{mod_name} failed to import: {e!r}")
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived:.6g}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((mod_name, repr(e)))
            print(f"{mod_name}.FAILED,0,0  # {e!r}", flush=True)
        print(f"# {mod_name} done in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
