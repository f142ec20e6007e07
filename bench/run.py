#!/usr/bin/env python
"""The chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Phases, all in this process (a chip belongs to one process):

1. device: JAX's platform, kind and count.  Anything but a TPU, or fewer
   chips than the cell asks for, exits non-zero with no result.
2. set-up: the cell's tables generated from ``--seed`` and direct-loaded
   into a durable ``Database`` (``bench/deploy.py``); the refresh stream
   planned; every launch shape the mix uses warmed through a warm-up
   ``QueryServer`` until a pass compiles nothing.  ``setup_s`` runs from
   process start to the window's start.
3. window: ``--seconds`` of the cell's traffic through
   ``QueryServer.submit`` → ``Ticket.result`` (``bench/window.py``);
   backend compiles inside it are counted.  ``--trace 1`` profiles the
   window and reads the per-layer metrics; ``--trace 0`` reads the
   end-to-end ones with the profiler off.
4. check: writes read back from the program, the program freed, then every
   answer compared with the plain reference at its snapshot
   (``bench/reference.py``), and the check's seconds, answers, distinct
   answers and those the reference's cube served logged.  Each number
   compared is printed beside its limit as the last lines on standard error
   and, last, in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``breakdown`` in a
traced run) and ``checks``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import deploy, reference, spec, stats, trace as tracemod  # noqa
from bench import traffic as trafficmod, window, work  # noqa: E402
from bench.record import Record  # noqa: E402

CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")
WARM_PASSES = ([0.0, 0.5, 1.0], [0.25, 0.75], [0.125, 0.375, 0.625, 0.875])


class NoChip(SystemExit):
    """The run found no accelerator, or too few chips for the cell."""


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']!r}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def warm(srv, sched: trafficmod.Schedule, tenants: List[str],
         clock: window.CompileClock, log: Callable[[str], None]) -> int:
    """Send each class to each tenant at points across its constant's range
    until a pass compiles nothing; returns the passes run."""
    for n, fractions in enumerate(WARM_PASSES, 1):
        c0 = clock.count
        for tenant in tenants:
            for k in range(len(sched.classes)):
                for f in fractions:
                    it = sched.probe(k, f, tenant)
                    srv.submit(it.query(), it.table, tenant=tenant,
                               **sched.hints).result(timeout=600)
        log(f"[warm] pass={n} compiles={clock.count - c0}")
        if clock.count == c0:
            return n
    return len(WARM_PASSES)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


def outstanding(queries: List[Dict[str, Any]], at: float) -> int:
    """Requests sent by ``at`` and not answered by then."""
    return sum(1 for q in queries
               if q["submitted"] is not None and q["submitted"] <= at
               and (q["done"] is None or q["done"] > at))


def lost_writes(applied: List[window.Applied], handles) -> int:
    """Acknowledged writes the program does not read back as written."""
    lost = 0
    for a in applied:
        if a.error is not None:
            continue
        h = handles[a.table]
        gone = {pk for _, pk in a.deletes}
        for _, row in a.inserts:
            if row["l_pk"] in gone:
                continue
            got = h.get(row["l_pk"])
            if got is None or any(reference._key(got[c]) !=
                                  reference._key(v) for c, v in row.items()):
                lost += 1
        for _, pk in a.deletes:
            if h.get(pk) is not None:
                lost += 1
    return lost


def check(cell: spec.Cell, seed: int, answers: List[Any],
          applied: List[window.Applied], lost: int,
          log: Callable[[str], None]):
    """Compare every answer with the reference at its snapshot; returns the
    numbers compared and the reference tables (for the kernel work)."""
    t0 = time.monotonic()
    refs: Dict[str, reference.RefTable] = {}
    for k, t in enumerate(cell.config["tables"]):
        refs[t["name"]] = reference.RefTable(deploy.generate(t, seed, k))
    for a in applied:
        if a.error is None:
            refs[a.table].inserts += a.inserts
            refs[a.table].deletes += a.deletes
    worst, wrong, missing, cubed = 0.0, 0, 0, 0
    memo: Dict[Any, Any] = {}
    for item, rows, ts in answers:
        if rows is None:
            missing += 1
            continue
        ref = refs[item.table]
        key = (item.table, item.ref, ref.seen(ts))
        if key not in memo:
            want = ref.from_cube(item.ref, ts)
            cubed += want is not None
            memo[key] = ref.direct(item.ref, ts) if want is None else want
        diff, err = reference.compare(item.ref, rows, memo[key])
        if diff is not None:
            wrong += 1
            log(f"[check] wrong answer #{item.index} {item.cls} "
                f"tenant={item.tenant}: {diff}")
        worst = max(worst, err)
    log(f"[check] seconds={time.monotonic() - t0} "
        f"answers={len(answers) - missing} distinct={len(memo)} "
        f"cube={cubed}")
    numbers = {"max_rel_err": worst, "wrong_answers": wrong,
               "unanswered": missing, "lost_writes": lost}
    return numbers, refs


def kernel_launches(answers, device_flags, refs, cell, peak) -> List[Dict]:
    """The work of every device-answered query (``bench/work.py``)."""
    out, zones, cards = [], {}, {}
    block_rows = int(cell.config["block_rows"])
    for (item, rows, ts), on_dev in zip(answers, device_flags):
        if not on_dev:
            continue
        ref, q = refs[item.table], item.ref
        n = ref.cols["l_pk"].shape[0]
        if q.column is not None:
            zk = (item.table, q.column)
            if zk not in zones:
                zones[zk] = work.zones(ref.cols[q.column], block_rows)
            n = work.unpruned_rows(zones[zk], q.lo, q.hi)
        g = []
        for col in q.group_by:
            if (item.table, col) not in cards:
                cards[(item.table, col)] = work.cardinality(ref.cols[col])
            g.append(cards[(item.table, col)])
        n_vals = len({c for _, c, _ in q.aggs if c is not None})
        b, ops = work.kernel_work(n, len(q.group_by), n_vals,
                                  work.groups_of(g))
        least, bound = work.least_time(b, ops, peak)
        out.append({"bytes": b, "ops": ops, "least_s": least,
                    "bound": bound})
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, require_tpu: bool = True,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result object (printing progress
    lines on standard output and the checks on standard error)."""
    t_start = T_START if t_start is None else t_start
    cell = spec.load_cell(root, workload)

    def log(line: str) -> None:
        print(line, flush=True)

    import jax
    info = device_info(cell.chips, require_tpu)
    peak = work.peaks(cell.bench_dir, info["kind"]) if traced else None
    from repro.compile_cache import enable_compile_cache
    from repro.core.serving import QueryServer
    log(f"[cache] {enable_compile_cache()}")
    clock = window.CompileClock()

    w = cell.traffic.get("writes")
    tables = {t["tenant"]: t for t in cell.config["tables"]}
    write_table = tables[w["tenant"]] if w else None
    t0 = time.monotonic()
    dep = deploy.build(cell.config, seed,
                       keep_keys=[write_table["name"]] if w else [])
    log(f"[setup] load_s={time.monotonic() - t0} "
        f"tables={len(dep.handles)} rows="
        f"{sum(h.store.baseline.nrows for h in dep.handles.values())}")
    sched = trafficmod.Schedule(cell.traffic, cell.queries, dep.tenants,
                                seed, seconds)
    refreshes = (trafficmod.plan_refreshes(
        cell.traffic, write_table, dep.keys[write_table["name"]], seed,
        seconds) if w else [])
    workers = int(cell.config.get("workers", 4))
    quotas = deploy.quotas(cell.config)
    t1 = time.monotonic()
    with QueryServer(dep.db, workers=workers, quotas=quotas) as wsrv:
        passes = warm(wsrv, sched, sched.tenant_names, clock, log)
    log(f"[setup] warm_s={time.monotonic() - t1} passes={passes} "
        f"compiles={clock.count} compile_s={clock.seconds}")

    spans = window.Spans(traced)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    srv = QueryServer(dep.db, workers=workers, quotas=quotas)
    try:
        spans.install()
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)
        c0 = clock.count
        setup_s = time.monotonic() - t_start
        with spans(tracemod.WINDOW_SPAN):
            win = window.run(srv, dep, sched, refreshes, seconds, spans)
        in_window = clock.count - c0
        if traced:
            t2 = time.monotonic()
            jax.profiler.stop_trace()
            stop_s = time.monotonic() - t2
    finally:
        spans.uninstall()
        srv.close()
    mem = memory_peak(jax.devices()[:cell.chips])
    queries = window.query_records(win)
    lost = lost_writes(win.applied, dep.handles)
    answers = []
    for s in win.sent:
        ok = s.error is None and s.ticket is not None
        rs = s.ticket.result(timeout=0) if ok else None
        answers.append((s.item, None if rs is None else rs.rows,
                        None if rs is None else rs.plan.ts))
    flags = [q["used_device"] for q in queries]
    dep.close()
    del dep, srv, win.sent
    log(f"[window] seconds={seconds} loop={sched.loop} sent={len(queries)} "
        f"answered={sum(q['answered'] for q in queries)} "
        f"compiles_in_window={in_window} refreshes={len(win.applied)}")
    if win.lateness_s:
        log(f"[window] generator lateness_s median="
            f"{stats.median(win.lateness_s)} max={max(win.lateness_s)}")
    log(f"[window] outstanding mid={outstanding(queries, win.start + seconds / 2)} "
        f"end={outstanding(queries, win.end)}")

    numbers, refs = check(cell, seed, answers, win.applied, lost, log)
    rfs = [{"kind": a.kind, "seconds": a.end - a.start, "error": a.error,
            "statements": len(a.inserts) + len(a.deletes)}
           for a in win.applied]
    rec = Record(loop=sched.loop, seconds=seconds, window_start=win.start,
                 setup_s=setup_s, queries=queries, refreshes=rfs,
                 spans=spans.durations)
    out: Dict[str, Any] = {}
    device = dict(info, memory_peak_bytes=mem)
    if traced:
        # one line, written as each phase ends, so that a run cut after
        # its window shows the phase it was in
        def part(text: str, end: str = " ") -> None:
            print(text, end=end, flush=True)

        part(f"[trace] stop_s={stop_s}")
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        t = rec.trace = tracemod.reduce(path)
        part(f"bytes={os.path.getsize(path)} parse_s={t.parse_s} "
             f"walk_s={t.walk_s} name_s={t.name_s} events={t.events} "
             f"gaps={t.gaps} spans={t.spans}")
        shutil.rmtree(tdir, ignore_errors=True)
        t3 = time.monotonic()
        rec.launches = kernel_launches(answers, flags, refs, cell, peak)
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        bounds = sorted({w_["bound"] for w_ in rec.launches})
        part(f"launches_s={time.monotonic() - t3} kernel_events={t.kernel_n} "
             f"device_answers={len(rec.launches)} roofline_bound={bounds}",
             end="\n")
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.metric_reader(cell.bench_dir, m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = numbers["unanswered"] + sum(a.error is not None
                                         for a in win.applied)
    out = {"correct": reference.verdict(numbers) and failed == 0,
           "attempted": len(queries) + len(win.applied),
           "failed": failed, "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": rec.trace.device_ops,
                            "idle_gaps": rec.trace.idle_gaps}
    out["checks"] = {k: {"value": v, "limit": reference.LIMITS[k]}
                     for k, v in numbers.items()}
    for k, v in numbers.items():
        print(f"[check] {k}={v} limit={reference.LIMITS[k]}",
              file=sys.stderr, flush=True)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    # the persistent compile cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"[device] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
