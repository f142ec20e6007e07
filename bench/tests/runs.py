"""A whole run of a tiny cell on the CPU, for the tests: the harness's look
for a chip is skipped and the compile cache is left as the test process
has it."""
from __future__ import annotations

import time

from bench import run
from bench.tests import fixture


def tiny_run(monkeypatch, tmp_path, workload: str, seconds: float = 1.5,
             traced: bool = False, seed: int = 2**31 + 11, **make):
    import repro.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "(unchanged)")
    root = fixture.make_root(str(tmp_path), **make)
    return run.run_cell(root, workload, seed, seconds, traced,
                        require_tpu=False, t_start=time.monotonic())
