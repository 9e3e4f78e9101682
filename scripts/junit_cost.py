#!/usr/bin/env python3
"""Where a pytest run's time went: ``scripts/junit_cost.py RUN.xml [N]``
sums a junit file's per-case ``time`` by test file and by test function and
prints the N dearest of each (docs/operations.md "What tier-1 costs")."""
import collections
import sys
import xml.etree.ElementTree as ET

cases = list(ET.parse(sys.argv[1]).getroot().iter("testcase"))
top = int(sys.argv[2]) if len(sys.argv) > 2 else 15
total = sum(float(c.get("time", 0)) for c in cases)
print(f"{len(cases)} cases, {total:.0f} s summed")


def file_of(case):
    return case.get("classname", "").split(".")[-1]


def function_of(case):
    return file_of(case) + "::" + case.get("name", "").split("[")[0]


for title, key in (("file", file_of), ("function", function_of)):
    cost, count = collections.Counter(), collections.Counter()
    for c in cases:
        cost[key(c)] += float(c.get("time", 0))
        count[key(c)] += 1
    print(f"-- by {title}")
    for name, s in cost.most_common(top):
        print(f"{s:8.1f} s {100 * s / total:5.1f}% {count[name]:4d} cases  "
              f"{name}")
