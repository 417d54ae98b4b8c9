"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]

Uses a scaled MiniCPM-family config (~100M params, WSD schedule — the
arch's assigned scheduler), the synthetic Zipf pipeline, AdamW, periodic
atomic checkpoints, and the fault-tolerant loop.
"""
import argparse
from dataclasses import replace

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.device import use_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.train_loop import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm_100m")
    args = ap.parse_args()
    use_compile_cache()

    # ~109M params: 12 layers x d768 of the minicpm family (CPU-trainable;
    # ~300 steps takes ~20-30 min on a 1-core container)
    cfg = replace(
        get_config("minicpm-2b"),
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=12, d_head=64,
        d_ff=2048, vocab=32000, dtype="float32", remat="none",
    )
    n_params = (cfg.vocab * cfg.d_model  # embed (tied head)
                + cfg.n_layers * (4 * cfg.d_model * cfg.d_model
                                  + 3 * cfg.d_model * cfg.d_ff))
    print(f"model: {cfg.name}-scaled, ~{n_params / 1e6:.0f}M params")

    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.batch,
                      vocab=cfg.vocab, seed=0)
    ocfg = AdamWConfig(lr=6e-4, schedule="wsd", warmup_steps=20,
                       total_steps=args.steps)
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=100, accum_steps=1)
    tr = Trainer(cfg, ocfg, dcfg, tcfg)
    tr.try_restore()
    hist = tr.run(args.steps - tr.step if tr.step < args.steps else 0)
    if hist:
        first = sum(h["loss"] for h in hist[:10]) / min(10, len(hist))
        last = sum(h["loss"] for h in hist[-10:]) / min(10, len(hist))
        dts = sorted(h["dt"] for h in hist)
        print(f"loss: {first:.3f} -> {last:.3f} over {len(hist)} steps "
              f"(median {dts[len(dts)//2]*1e3:.0f} ms/step)")
        assert last < first, "loss must decrease"
    print("OK")


if __name__ == "__main__":
    main()
