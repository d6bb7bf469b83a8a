# One function per paper claim / system layer. Prints
# ``name,us_per_call,derived`` CSV (see each module for what is measured).
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import expocloud_bench, kernel_bench, roofline_bench, \
        train_bench

    rows = []
    failed = []
    for mod in (expocloud_bench, kernel_bench, train_bench, roofline_bench):
        try:
            rows.extend(mod.run_all())
        except Exception as e:  # noqa: BLE001 — report, continue, fail
            rows.append((f"{mod.__name__}_FAILED", 0.0, repr(e)[:80]))
            failed.append(mod.__name__)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
