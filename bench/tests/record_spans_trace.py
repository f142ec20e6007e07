#!/usr/bin/env python
"""Record the small trace with the program's spans that
``test_bench_spans.py`` reads, on a TPU:

    python3 bench/tests/record_spans_trace.py tiny_spans.xplane.pb
    gzip -9 -c tiny_spans.xplane.pb > bench/tests/data/tiny_spans.xplane.pb.gz

A traced run of the tests' tiny closed-loop cell (0.3 s window) with the
program's span recorder on (``bench/run_spans.py``), whose ``.xplane.pb``
is copied to the path given before the harness deletes it.
"""
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, run_spans  # noqa: E402
from bench.tests import fixture  # noqa: E402


def main() -> None:
    dst = sys.argv[1]
    got = {}
    root = fixture.make_root(tempfile.mkdtemp())
    with run_spans.wrapped(got, keep_trace=dst):
        out = run.run_cell(root, "tiny-closed", 7, 0.3, True)
    print(json.dumps(out), flush=True)
    print(json.dumps(run_spans.numbers(got)), flush=True)
    print(f"[trace] {dst} bytes={os.path.getsize(dst)}", flush=True)


if __name__ == "__main__":
    main()
