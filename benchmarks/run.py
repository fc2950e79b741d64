# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None,
        help="substring filter on benchmark function names",
    )
    args = ap.parse_args()

    from . import paper_figures

    fns = list(paper_figures.ALL)
    if args.only:
        fns = [f for f in fns if args.only in f.__name__]

    print("name,us_per_call,derived")
    failed = 0
    for fn in fns:
        try:
            fn().emit()
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{fn.__name__},nan,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
