"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  MEASURED rows are real timings on
this host; MODELED rows come from the calibrated simulator (see
benchmarks/simlib.py docstring for the calibration anchors).  The roofline
tables live in ``benchmarks/roofline.py`` (run separately: they need 512
host devices, while these benches must see the real single device).
"""
from __future__ import annotations

import traceback
from typing import List


def main() -> int:
    from benchmarks import (
        channels,
        elastic_sched,
        elasticity,
        isolation,
        tail_latency,
    )

    rows: List[dict] = []
    failed = []
    for mod in (tail_latency, isolation, elasticity, elastic_sched, channels):
        try:
            mod.run(rows)
        except Exception:
            # keep measuring the other sections; the exit code reports it
            traceback.print_exc()
            failed.append(mod.__name__)
            rows.append({
                "name": f"{mod.__name__}/ERROR",
                "us_per_call": -1,
                "derived": "crashed",
            })

    print("name,us_per_call,derived")
    for r in rows:
        d = str(r["derived"]).replace(",", ";")
        print(f"{r['name']},{r['us_per_call']:.3f},{d}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
