"""§Perf hillclimbing driver — the three chosen cells, per the assignment:

  1. xlstm-350m    x train_4k  — worst roofline fraction (TP overhead swamps
                                 a 350M model)        -> variant 'dp_only'
  2. qwen3-moe     x train_4k  — most collective-bound (EP dispatch bytes
                                 x3 from remat)       -> variant 'save_moe'
  3. mistral-large x train_4k  — most paper-representative (canonical dense
                                 GEMM TP pairs)       -> variant 'seq_parallel'

Each variant is LOWERED FOR REAL on the single-pod mesh and its HLO
collective bytes / memory compared against the base cell (per-body HLO is a
valid A/B because the loop structure is unchanged).  Results land in
hillclimb_out/ and are summarized in EXPERIMENTS.md §Perf.
"""
import json
import os
import traceback
from pathlib import Path

CELLS = [
    ("xlstm-350m", "train_4k", "dp_only"),
    ("qwen3-moe-235b-a22b", "train_4k", "save_moe"),
    ("mistral-large-123b", "train_4k", "seq_parallel"),
    # beyond the required three: the worst remaining memory cell
    ("jamba-1.5-large-398b", "train_4k", "accum4"),
    ("jamba-1.5-large-398b", "train_4k", "layer_remat"),
]


def main() -> None:
    from .dryrun import HOST_DEVICES_FLAG, lower_cell

    os.environ.setdefault("XLA_FLAGS", HOST_DEVICES_FLAG)

    out = Path("hillclimb_out")
    out.mkdir(exist_ok=True)
    for arch, shape, variant in CELLS:
        for v in ("base", variant):
            tag = f"{arch}__{shape}__{v}"
            p = out / f"{tag}.json"
            if p.exists():
                print(f"[skip-cached] {tag}")
                continue
            print(f"[lower] {tag}", flush=True)
            try:
                rec = lower_cell(arch, shape, multi_pod=False, variant=v)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "variant": v,
                       "status": "failed", "error": repr(e)[:400]}
            p.write_text(json.dumps(rec, indent=1))
            print(f"[done] {tag}: {rec['status']}", flush=True)

    # summary
    print(f"\n{'cell':40s} {'variant':14s} {'coll GB':>9s} {'temp GiB':>9s} {'args GiB':>9s}")
    for arch, shape, variant in CELLS:
        for v in ("base", variant):
            p = out / f"{arch}__{shape}__{v}.json"
            if not p.exists():
                continue
            r = json.loads(p.read_text())
            if r["status"] != "ok":
                print(f"{arch + ' ' + shape:40s} {v:14s} {r['status']}")
                continue
            print(f"{arch + ' ' + shape:40s} {v:14s} "
                  f"{r['collective_total'] / 1e9:9.1f} "
                  f"{r['temp_size_in_bytes'] / 2**30:9.1f} "
                  f"{r['argument_size_in_bytes'] / 2**30:9.1f}")


if __name__ == "__main__":
    main()
