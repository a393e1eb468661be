"""PR 45's record of a tier-1 run: from the junit file the driver's command writes (``--junitxml``), the run's own
line (tests, failures, seconds) and the heaviest files by the summed time of their tests: under ``--dist loadfile`` a
file is what one worker carries, so the heaviest file bounds the run from below.

    python benchmarks/calls/pr45_suite_times.py /tmp/_t1.xml [files to list test by test]
"""
import collections
import sys
import xml.etree.ElementTree as ET

root = ET.parse(sys.argv[1]).getroot()
suite = root if root.tag == "testsuite" else root.find("testsuite")
files, tests = collections.defaultdict(lambda: [0.0, 0]), []
for case in root.iter("testcase"):
    name = case.get("classname").split(".")[1]
    files[name][0] += float(case.get("time"))
    files[name][1] += 1
    tests.append((float(case.get("time")), name, case.get("name")))
print({k: suite.get(k) for k in ("tests", "errors", "failures", "skipped", "time")})
print(f"all {len(files)} files: {sum(t for t, _ in files.values()):.0f} s on one worker")
for name, (seconds, count) in sorted(files.items(), key=lambda kv: -kv[1][0])[:12]:
    print(f"{name:28s} {seconds:6.0f} s {count:4d} tests")
for wanted in sys.argv[2:]:
    print("==", wanted)
    for seconds, name, test in sorted(tests, reverse=True):
        if name == wanted:
            print(f"  {seconds:7.1f} {test}")
