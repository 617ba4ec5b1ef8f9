"""End-to-end training driver: a ~100M-param llama-style model trained for
a few hundred steps on CPU with the full production stack — sharded data
pipeline, AdamW, checkpointing, fault-tolerant loop (with an injected
failure to prove restart works).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import dataclasses
import tempfile

import jax

from repro.configs.base import ModelConfig, RunConfig, ShapeCell
from repro.data.pipeline import DataShard, _batch_for_step
from repro.models import zoo
from repro.models.params import count_params, init_params
from repro.runtime.fault import FaultConfig, run_training
from repro.train.step import build_train_step, init_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    # ~100M params: 12L x 512d x 8H, 50k vocab
    cfg = ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=512,
        n_heads=8, n_kv_heads=8, d_ff=2048, vocab=50_257, head_dim=64,
        attn_impl="dense", remat="none", dtype="float32")
    run = RunConfig(optimizer="adamw", learning_rate=3e-4)
    cell = ShapeCell("train", args.seq, args.batch, "train")

    specs = zoo.model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0), cfg.dtype)
    print(f"model: {count_params(specs)/1e6:.1f}M params")
    state = init_state(cfg, run, params)
    step = jax.jit(build_train_step(cfg, run, total_steps=args.steps))

    def batches(s: int):
        return _batch_for_step(s, DataShard(0, 1), cfg.vocab, args.batch,
                               args.seq)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        fc = FaultConfig(ckpt_dir=ckpt_dir, ckpt_every=50,
                         inject_failures_at=(args.steps // 2,))
        state, stats = run_training(step, state, batches, args.steps, fc)
    first = sum(stats.losses[:10]) / max(len(stats.losses[:10]), 1)
    last = sum(stats.losses[-10:]) / max(len(stats.losses[-10:]), 1)
    print(f"steps={stats.steps_run} restarts={stats.restarts} "
          f"(1 injected failure survived)")
    print(f"loss: first10={first:.3f} -> last10={last:.3f}")
    assert last < first, "model must learn"
    print("OK")


if __name__ == "__main__":
    from repro.env import use_compile_cache
    use_compile_cache()
    main()
