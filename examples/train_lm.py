"""End-to-end training driver: ~100M-parameter LM for a few hundred steps.

This is the workload the paper's pipeline feeds (xLUNGS: radiomics features
-> AI model training).  It exercises the full production stack on any
device count: config system -> model zoo -> AdamW(+WSD) -> jitted train
step with explicit shardings -> fault-tolerant Trainer (async atomic
checkpoints, auto-resume, straggler log, SIGTERM emergency save).

    PYTHONPATH=src python examples/train_lm.py --steps 300
    PYTHONPATH=src python examples/train_lm.py --arch granite-3-2b --smoke

Kill it mid-run and start it again: it resumes from the latest committed
checkpoint.  ``--smoke`` shrinks the model for a fast CPU sanity pass.
"""
import argparse

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.models.registry import get_config, get_model
from repro.runtime.compile_cache import use_compile_cache
from repro.train.trainer import Trainer

# qwen3-family config scaled to ~100M params (d=512, L=8, untied embeddings)
M100 = dict(
    n_layers=8, d_model=512, n_heads=8, n_kv_heads=4, head_dim=64,
    d_ff=1536, vocab_size=32_000, dtype="float32",
)


def synthetic_batches(vocab_size: int, batch: int, seq: int, seed: int = 0):
    """Deterministic synthetic LM stream with learnable n-gram structure."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, vocab_size, size=(64, seq + 1))
    while True:
        rows = rng.integers(0, base.shape[0], size=batch)
        noise = rng.integers(0, vocab_size, size=(batch, seq + 1))
        keep = rng.random((batch, seq + 1)) < 0.9
        tokens = np.where(keep, base[rows], noise)
        yield {"tokens": jax.numpy.asarray(tokens[:, : seq + 1], jax.numpy.int32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="/tmp/repro_train_lm")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + 5 steps (CI-speed sanity check)")
    args = ap.parse_args()
    use_compile_cache()

    base = get_config(args.arch)
    if args.smoke:
        cfg = base.reduced()
        steps = 5
    else:
        cfg = base.reduced(**M100)
        steps = args.steps
    model = get_model(cfg)
    print(f"arch={cfg.name} family={cfg.family} params~{cfg.n_params/1e6:.1f}M "
          f"steps={steps} devices={jax.device_count()}")

    run = RunConfig(
        steps=steps, learning_rate=3e-4, warmup_steps=max(2, steps // 20),
        schedule="wsd", checkpoint_every=max(1, steps // 4),
        async_checkpoint=True,
    )
    data = synthetic_batches(cfg.vocab_size, args.batch, args.seq)
    trainer = Trainer(model, run, data, args.workdir)
    params, _, last = trainer.train(steps=steps)
    print(f"final: step={last['step']} loss={last['loss']:.4f} "
          f"median_step_s={trainer.straggler.median:.3f}")


if __name__ == "__main__":
    main()
