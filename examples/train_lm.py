"""End-to-end training driver: hash-deduped data plane -> LM -> AdamW, with
checkpointing and injected-failure recovery.

Quick demo (~3 min on CPU):
    PYTHONPATH=src python examples/train_lm.py
The ~100M-parameter configuration from the assignment:
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse
import dataclasses
import shutil

from repro.configs.base import LayerSpec, ModelConfig
from repro.configs.registry import get_config
from repro.data.pipeline import PipelineConfig
from repro.train.fault import FailureInjector
from repro.train.loop import LoopConfig, train
from repro.train.optim import Schedule
from repro.compile_cache import enable_compile_cache

TINY = ModelConfig(
    name="demo-6m", n_layers=4, d_model=256, vocab=8192, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=1024, unit=(LayerSpec("attn", "dense"),),
    q_chunk=128, kv_chunk=128, param_dtype="float32",
    activation_dtype="float32")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="demo", choices=["demo", "100m"])
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--inject-failure", action="store_true", default=True)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = TINY if args.preset == "demo" else get_config("paper-tiny")
    if args.preset == "100m":
        args.seq, args.batch = 1024, 8
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    pipe = PipelineConfig(seq_len=args.seq, batch_size=args.batch,
                          vocab=cfg.vocab, dedup=True, seed=0)
    loop = LoopConfig(n_steps=args.steps, ckpt_every=25,
                      ckpt_dir=args.ckpt_dir, log_every=10)
    sched = Schedule(peak_lr=3e-3 if args.preset == "demo" else 6e-4,
                     warmup_steps=20, decay_steps=args.steps)
    injector = (FailureInjector(fail_at_steps=(args.steps // 2,))
                if args.inject_failure else None)

    print(f"model={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")
    res = train(cfg, pipe, loop, schedule=sched, injector=injector)

    first, last = res["losses"][0], sum(res["losses"][-10:]) / 10
    print(f"\nloss {first:.3f} -> {last:.3f}  "
          f"(restarts={res['restarts']}, stragglers={len(res['stragglers'])})")
    print("data plane:", res["telemetry"])
    assert last < first, "training must reduce the loss"
    if injector:
        assert res["restarts"] >= 1, "failure-recovery path must have fired"
    print("OK")


if __name__ == "__main__":
    main()
