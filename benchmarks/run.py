"""Benchmark harness: one function per paper table + system benches.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  paper_tables    -- Tables II..X area/timing reproductions (area model)
  kernel_bench    -- core/kernel/system microbenchmarks
  roofline_report -- dry-run roofline summary (reads experiments/dryrun)
"""
import sys


def main() -> int:
    """Run every section; the exit status is 1 if any benchmark raised."""
    from repro.kernels import runtime
    runtime.enable_compilation_cache()
    print("name,us_per_call,derived")
    from . import paper_tables, kernel_bench, roofline_report
    failed = []
    for section in (paper_tables, kernel_bench, roofline_report):
        for fn in section.ALL:
            try:
                fn()
            except Exception as e:      # a bench failure must not hide others
                name = f"{section.__name__}.{fn.__name__}"
                failed.append(name)
                print(f"{name},0.00,ERROR:{e!r}", file=sys.stdout)
    if failed:
        print(f"{len(failed)} benchmark(s) failed: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
