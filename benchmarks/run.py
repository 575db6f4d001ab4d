# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["paper", "search", "serving"],
                    default=None)
    ap.add_argument("--rebuild", action="store_true",
                    help="drop the cached corpus/graph/index artifacts and "
                         "rebuild from scratch (stamps normally rebuild "
                         "only on a build-params mismatch)")
    args = ap.parse_args(argv)
    if args.rebuild:
        from benchmarks import common
        common.force_rebuild()
    rows = []
    if args.only in (None, "paper"):
        from benchmarks.bench_paper import all_benchmarks as paper
        rows += paper()
    if args.only in (None, "search"):
        from benchmarks.bench_search import all_benchmarks as search
        rows += search()
    if args.only in (None, "serving"):
        from benchmarks.bench_serving import all_benchmarks as serving
        rows += serving()
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
