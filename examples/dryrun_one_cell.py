"""Lower + compile one (arch x shape) cell on the production mesh and print
its roofline terms.  This is the per-cell version of repro.launch.dryrun.

Run:  PYTHONPATH=src python examples/dryrun_one_cell.py --arch mixtral-8x7b \
          --shape train_4k [--multi-pod]
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    from repro.device import use_compile_cache
    from repro.launch.dryrun import lower_cell
    from repro.launch.roofline import analyse_cell, param_counts, advice

    use_compile_cache()
    rec = lower_cell(args.arch, args.shape, args.multi_pod)
    if rec["status"] != "ok":
        print(rec)
        return
    cell = analyse_cell(rec, {args.arch: param_counts(args.arch)})
    print(f"\n{args.arch} x {args.shape} on {rec['mesh']}:")
    print(f"  compute    {cell.compute_s:.3e} s")
    print(f"  memory     {cell.memory_s:.3e} s")
    print(f"  collective {cell.collective_s:.3e} s")
    print(f"  dominant:  {cell.dominant}  (useful ratio {cell.useful_ratio:.2f})")
    print(f"  advice:    {advice(cell)}")


if __name__ == "__main__":
    main()
