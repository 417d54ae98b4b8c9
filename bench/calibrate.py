"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

In one process, at the cell's own sizes: for each of ``--seeds`` seeds, the
widest gap of every program's output (the compiled callable the window
drives, on that seed's inputs) from the float32 reference; and for each of
``--control-seeds`` seeds, the same gap of the control, which is the
reference itself one precision step below the configuration's (bfloat16,
``control_compile``), put in the program's place.  A
limit lies between the largest sound reading and the smallest control
reading.  Writes ``bench/out/calibrate.<cell>.json`` and prints a summary.
The benchmark's own runs never run the control.

A serving cell (a configuration whose ``builder`` is ``serving``) is
calibrated by ``serving.calibrate``: each seed serves the cell's traffic
for a window of ``--window`` seconds, and its served tokens are read
against the reference, and, on the first ``--control-seeds`` seeds, so are
the tokens that the control and each fault the configuration lists put
first at the same positions.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    import os

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def control_compile(cell: harness.Cell):
    """A ``compile_fn`` for ``harness.run_cell`` that puts the reference in
    the program's place, one precision step below the float32 that the
    configurations state: bfloat16 arrays and arithmetic."""
    def compile_fn(progs):
        import jax
        import jax.numpy as jnp

        fns = []
        for p in progs:
            def fn(x, p=p):
                low = {k: v.astype(jnp.bfloat16) for k, v in x.items()}
                out = cell.reference(p.name, low, p.sizes)
                return {k: v.astype(jnp.float32) for k, v in out.items()}
            fns.append(jax.jit(fn))
        return fns, [SimpleNamespace(nests=[]) for _ in progs], None
    return compile_fn


def readings(cell, progs, fns, seeds) -> dict[str, list[float]]:
    """Widest gap of each program's output from the reference, per seed."""
    import numpy as np

    out: dict[str, list[float]] = {p.name: [] for p in progs}
    for seed in seeds:
        for p, fn in zip(progs, fns):
            x = harness.make_inputs(cell, p, seed)
            got = {k: np.asarray(v) for k, v in fn(x).items() if k in p.outputs}
            del x
            out[p.name].append(harness.compare(got, harness.reference_outputs(cell, p, seed),
                                               p.outputs))
    return out


def calibrate_serving(cell: harness.Cell, args) -> int:
    from bench import serving

    t0 = time.perf_counter()
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    per_seed = serving.calibrate(cell, seeds, seeds[:args.control_seeds], args.window)
    names = sorted({k for r in per_seed.values() for k in r
                    if k not in ("failed_requests", "checked_tokens", "checked_lengths")})
    summary = {"logit_gap": {"sound_max": max(r["logit_gap"] for r in per_seed.values()),
                             "limit": cell.config["limits"]["logit_gap"]}}
    for name in names:
        if name != "logit_gap":
            summary[name] = {"min": min(r[name] for r in per_seed.values() if name in r)}
    harness.OUT_DIR.mkdir(parents=True, exist_ok=True)
    with open(harness.OUT_DIR / f"calibrate.{cell.name}.json", "w") as f:
        json.dump({"cell": cell.name, "seeds": seeds, "window_s": args.window,
                   "readings": {str(k): v for k, v in per_seed.items()}, "summary": summary,
                   "seconds": time.perf_counter() - t0, "device": harness.device_info()},
                  f, indent=1)
    print(json.dumps(summary))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    ap.add_argument("--window", type=float, default=20.0,
                    help="seconds of traffic per seed (serving cells)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if harness.device_info()["platform"] != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    if "builder" in cell.config:
        return calibrate_serving(cell, args)
    harness.use_precision(cell.config)
    t0 = time.perf_counter()
    progs = harness.build_programs(cell.config, cell.traffic)
    fns, _, _ = harness.compile_programs(progs)
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    sound = readings(cell, progs, fns, seeds)
    cfns, _, _ = control_compile(cell)(progs)
    cseeds = [args.first_seed + 104729 * (k + 1) for k in range(args.control_seeds)]
    control = readings(cell, progs, cfns, cseeds)
    summary = {}
    for p in progs:
        lo, up = max(sound[p.name]), min(control[p.name])
        summary[p.name] = {"lower": lo, "upper": up, "ratio": up / lo if lo else None,
                           "limit": cell.config["limits"][p.name]}
        print(f"calibrate: {p.name}: sound max {lo!r}, control min {up!r}, "
              f"limit {cell.config['limits'][p.name]!r}", flush=True)
    harness.OUT_DIR.mkdir(parents=True, exist_ok=True)
    obj = {"cell": cell.name, "seeds": seeds, "control_seeds": cseeds, "sound": sound,
           "control": control, "summary": summary, "seconds": time.perf_counter() - t0,
           "device": harness.device_info()}
    with open(harness.OUT_DIR / f"calibrate.{cell.name}.json", "w") as f:
        json.dump(obj, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
