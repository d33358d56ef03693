#!/usr/bin/env python
"""Where a test run's seconds went, read from its junit file.

    python tools/test_seconds.py [/tmp/_t1.xml] [--over 10]

Prints seconds and tests by file, the tests over ``--over`` seconds, and the
sums for ``tests/perfbench`` (the benchmark's own) and for the rest. The
driver's command (``/root/TESTS_LAST_RUN.json``) writes ``/tmp/_t1.xml``; under
``-n 6 --dist loadfile`` a file is one worker's, so the wall time is near the
sum over six and never under the longest file.
"""

import argparse
import collections
import xml.etree.ElementTree as ET


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("junit", nargs="?", default="/tmp/_t1.xml")
    ap.add_argument("--over", type=float, default=10.0)
    args = ap.parse_args()

    by_file = collections.defaultdict(lambda: [0.0, 0])
    tests = []
    for case in ET.parse(args.junit).getroot().iter("testcase"):
        # classname: tests.unit.test_x[.TestClass]; the file is up to test_*
        parts = case.get("classname", "").split(".")
        stop = next((i for i, p in enumerate(parts) if p.startswith("test_")),
                    len(parts) - 1)
        path = "/".join(parts[:stop + 1]) + ".py"
        secs = float(case.get("time", 0))
        by_file[path][0] += secs
        by_file[path][1] += 1
        tests.append((secs, f"{path}::{'::'.join(parts[stop + 1:] + [case.get('name')])}"))

    print(f"{'seconds':>9} {'tests':>6}  file")
    for path, (secs, n) in sorted(by_file.items(), key=lambda kv: -kv[1][0]):
        print(f"{secs:9.1f} {n:6d}  {path}")
    slow = sorted((t for t in tests if t[0] >= args.over), reverse=True)
    print(f"\n{len(slow)} tests of {args.over:g} s or more, "
          f"{sum(s for s, _ in slow):.0f} s:")
    for secs, name in slow:
        print(f"{secs:9.1f}  {name}")
    bench = [v for k, v in by_file.items() if k.startswith("tests/perfbench/")]
    rest = [v for k, v in by_file.items() if not k.startswith("tests/perfbench/")]
    print()
    for label, part in (("tests/perfbench", bench), ("the rest", rest),
                        ("all", bench + rest)):
        print(f"{sum(s for s, _ in part):9.1f} {sum(n for _, n in part):6d}  {label}")


if __name__ == "__main__":
    main()
