#!/usr/bin/env python
"""Record the small trace that ``test_bench_trace.py`` reads, on a TPU:

    python3 bench/tests/record_trace.py tiny.xplane.pb
    gzip -9 -c tiny.xplane.pb > bench/tests/data/tiny.xplane.pb.gz

A traced run of the tests' tiny closed-loop cell (0.3 s window) whose
``.xplane.pb`` is copied to the path given before the harness deletes it.
"""
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from bench.tests import fixture  # noqa: E402


def main() -> None:
    dst = sys.argv[1]
    reduce = run.tracemod.reduce

    def keep(path):
        shutil.copy(path, dst)
        return reduce(path)

    run.tracemod.reduce = keep
    root = fixture.make_root(tempfile.mkdtemp())
    out = run.run_cell(root, "tiny-closed", 7, 0.3, True)
    print(out, flush=True)
    print(f"[trace] {dst} bytes={os.path.getsize(dst)}", flush=True)


if __name__ == "__main__":
    main()
