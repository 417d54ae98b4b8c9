"""The knee of a serving cell: its traffic offered at several fixed rates.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5 --seconds 51

In one process (weights, engine and warm-up once), for each rate in turn:
the cell's traffic at that rate, its lead-in and a window of ``--seconds``,
then every request still open cancelled and the engine drained.  Per rate:
requests due and finished in the window, time to first token and time per
output token (TPOT, a request's mean gap between tokens) at their median
and 90th percentile, the 95th and 99th percentile gap between tokens,
tokens/s, the share of requests meeting both of the traffic's ``slo``
limits (TTFT and TPOT), the share whose every gap meets the TPOT limit,
and the backlog (requests waiting for a slot) at the window's middle and
close.  The knee is the highest rate at which at least 90% of requests
meet both limits with no growing backlog.  Writes
``bench/out/sweep.<cell>.json``.  Not part of any run of the benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    import os

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, serving  # noqa: E402
from bench.serve_counts import nearest_rank  # noqa: E402


def backlog(loop: serving.OpenLoop) -> int:
    return sum(r.handle.state.value == "queued" for r in loop.active)


def one_rate(eng, cell, seed: int, rate: float, seconds: float) -> dict:
    traffic = cell.traffic
    reqs = serving.schedule(traffic, cell.config["model"]["vocab"], seed, seconds, False,
                            rate=rate)
    t0 = time.perf_counter()
    w0 = t0 + float(traffic["lead_in_s"])
    loop = serving.OpenLoop(eng, reqs, t0)
    loop.run(w0 + seconds / 2)
    mid = backlog(loop)
    loop.run(w0 + seconds)
    end = backlog(loop)
    for r in loop.active:
        r.handle.cancel()
    while eng.step():
        pass
    rec = serving.window_record(reqs, t0, w0, w0 + seconds, cell.config["model"], traffic)
    limit = traffic["slo"]["tpot_ms"]
    due = [r for r in reqs if r.phase == "window"]
    every_gap = sum(len(r.times) > 1 and max(b - a for a, b in zip(r.times, r.times[1:]))
                    * 1e3 <= limit for r in due)
    return {"rate_per_s": rate, "requests_due": rec["requests_due"],
            "requests_finished": rec["requests_finished"],
            "ttft_ms": {q: nearest_rank(rec["ttft_ms"], q) for q in (50, 90)},
            "tpot_ms": {q: nearest_rank(rec["tpot_ms"], q) for q in (50, 90)},
            "itl_ms": {q: nearest_rank(rec["itl_ms"], q) for q in (50, 95, 99)},
            "tokens_per_s": rec["tokens_in_window"] / seconds,
            "slo_met_share": rec["slo_met_share"],
            "every_gap_met_share": 100.0 * every_gap / max(1, len(due)),
            "backlog_mid": mid, "backlog_end": end,
            "lateness_ms": rec["lateness_ms"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if harness.device_info()["platform"] != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    cfg = serving.model_config(cell.config)
    params = serving.make_weights(cfg, args.seed)
    eng = serving.make_engine(cfg, params, cell.config["serve"])
    serving.warm_up(eng, cell.traffic, cell.config["serve"])
    rows = []
    for rate in (float(x) for x in args.rates.split(",")):
        rows.append(one_rate(eng, cell, args.seed, rate, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    harness.OUT_DIR.mkdir(parents=True, exist_ok=True)
    with open(harness.OUT_DIR / f"sweep.{cell.name}.json", "w") as f:
        json.dump({"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
                   "device": harness.device_info(), "rates": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
