"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress lines starting ``bench:``, then, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; its last key,
``checks``, gives each number compared with its limit, and the same
numbers close standard error.  Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.  Per-program
tables go to ``bench/out/``; JAX's compile cache to ``.jax_cache/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise log to a fixed directory outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
