"""Find the knee of an open-loop cell: serve it at a ladder of rates.

    python bench/sweep.py --workload register10m.point --seed 11 \\
        --seconds 15 --rates 100,200,400,800,1600

One process: the data and the network are made once from the seed, and
each rate gets a fresh frontend, its warm-up and one measured window on
that network. For each
rate it prints p50 and p95 over all requests due (failures count as
missing), how many were answered, and whether the backlog grew: the
median latency of the window's last quarter against its first quarter,
and how many requests were still unanswered when the window closed.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402
from run import JAX_CACHE, ROOT, latency, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    import jax

    from repro.core import compile_cache

    if jax.devices()[0].platform == "cpu" and not args.rehearse:
        log("sweep: no accelerator found")
        return 2
    compile_cache.enable()
    cell = run.Cell(args.workload, ROOT)
    prepared = run.prepare(cell, args.seed, args.rehearse)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        out = run.run_cell(cell, args.seed, args.seconds, False,
                           rehearse=args.rehearse,
                           mix_update={"rate_per_s": rate},
                           prepared=prepared)
        due = [r for r in out["rows"] if r["due"] < out["t0"] + args.seconds]
        ok = [r for r in due if r["status"] == "ok"]
        lat = [(r["recv"] - r["due"]) * 1e3 for r in ok]
        failed = len(due) - len(ok)
        q = max(len(ok) // 4, 1)
        by_due = sorted(ok, key=lambda r: r["due"])
        head = statistics.median((r["recv"] - r["due"]) * 1e3
                                 for r in by_due[:q])
        tail = statistics.median((r["recv"] - r["due"]) * 1e3
                                 for r in by_due[-q:])
        open_at_close = sum(1 for r in due
                            if r["recv"] > out["t0"] + args.seconds)
        row = {"rate_per_s": rate, "due": len(due), "answered": len(ok),
               "p50_ms": latency.percentile(lat, failed, 50),
               "p95_ms": latency.percentile(lat, failed, 95),
               "first_quarter_p50_ms": head, "last_quarter_p50_ms": tail,
               "open_at_close": open_at_close,
               "correct": out["line"]["correct"]}
        rows.append(row)
        print(json.dumps({"sweep": row}), flush=True)
    print(json.dumps({"sweep_done": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
