#!/usr/bin/env python3
"""Find the knee of an open-loop cell: set the cell up once, then offer its
traffic at each of a list of rates and report what the engine sustained.

    python3 bench/sweep.py --workload deep96.narrow --seed 5 --seconds 8 \\
        --rates 200,400,800,1600

A rate is sustained when the answers keep pace with the arrivals (at most
5% of the window's queries still unanswered when it closes) and the p95
latency of the window's last quarter stays within twice that of its first
plus 10 ms (no growing backlog).  The
highest sustained rate is the knee; a cell is offered 0.8 of it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, load, stats, traffic
    spec = harness.load_spec()
    cell = harness.resolve_cell(spec, args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    if cell.mix["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    cell.mix = dict(cell.mix, rate=max(rates))
    total = args.seconds * len(rates)
    sv = harness.set_up(cell, args.seed, total)
    rows = []
    for i, rate in enumerate(rates):
        mix = dict(cell.mix, rate=rate)
        sched = traffic.make_schedule(mix, args.seed + i,
                                      traffic.ops_needed(mix, args.seconds))
        sv.traffic.prepare(sched)
        c0 = sv.compiles.count
        ses = load.run_open(sv.engine, sv.traffic, sched,
                            time.perf_counter() + 0.05,
                            float(mix["settle_s"]), args.seconds)
        lat = ((ses.done - ses.due) * 1e3)[ses.ok]
        quarter = max(len(lat) // 4, 1)
        early = stats.percentile(lat[:quarter], 95)
        late = stats.percentile(lat[-quarter:], 95)
        answered = int((ses.ok & (ses.done <= ses.end)).sum())
        row = {"rate": rate, "offered": len(ses) / args.seconds,
               "answered_in_window": answered / args.seconds,
               "ops_done": int(ses.ok.sum()),
               "p50_ms": stats.percentile(lat, 50),
               "p95_ms": stats.percentile(lat, 95), "p95_first_q_ms": early,
               "p95_last_q_ms": late,
               "compiles": sv.compiles.count - c0,
               "sustained": bool(late <= 2 * early + 10 and len(lat)
                                 - answered <= max(5, 0.05 * len(lat)))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    sv.engine.close()
    knee = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"workload": args.workload, "knee": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
