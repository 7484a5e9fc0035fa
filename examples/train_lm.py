"""End-to-end driver: train a ~100M-param LM for a few hundred steps on
synthetic Markov data, with checkpointing (deliverable-(b) driver).

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--tiny]

The default config is a genuine ~105M-parameter llama-family model
(8L, d=768, 12H/4kv, d_ff=2048, 32k vocab).  A few hundred steps take
a couple of hours on one CPU core -- pass --tiny for a minutes-scale
smoke of the same driver.  Kill and re-run to see checkpoint-restart
continue the curve.
"""
import argparse

import jax

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.distributed.sharding import ShardingRules
from repro.models import get_model
from repro.models import params as pm
from repro.training import checkpoint as ckpt
from repro.training import optimizer as opt
from repro.training.data import SyntheticData
from repro.training.train_step import make_train_step
from repro.jax_cache import enable_persistent_cache


def lm_100m():
    return get_config("tinyllama-1.1b").replace(
        name="llama-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=4, d_ff=2048, vocab=32000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = lm_100m()
    if args.tiny:
        cfg = cfg.replace(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                          d_ff=256, vocab=2048)
    model = get_model(cfg)
    nparams = pm.count_params(model.specs())
    print(f"{cfg.name}: {nparams/1e6:.1f}M params")

    shape = ShapeSpec("train", seq_len=128, global_batch=16, kind="train")
    data = SyntheticData(cfg, shape)
    ocfg = opt.AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    rules = ShardingRules()

    params = pm.materialize(model.specs(), jax.random.PRNGKey(0))
    state = opt.init(params)
    start = 0
    if ckpt.latest_step(args.ckpt_dir) is not None:
        (params, state), start = ckpt.restore(args.ckpt_dir, (params, state))
        print(f"resumed from step {start}")

    step = jax.jit(make_train_step(model, ocfg, rules))
    for i in range(start, args.steps):
        params, state, mets = step(params, state, data.batch_at(i))
        if (i + 1) % 20 == 0 or i == start:
            print(f"step {i+1:4d} loss={float(mets['loss']):.4f} "
                  f"gnorm={float(mets['grad_norm']):.3f}", flush=True)
        if (i + 1) % 100 == 0:
            ckpt.save(args.ckpt_dir, i + 1, (params, state))
    ckpt.save(args.ckpt_dir, args.steps, (params, state))
    print(f"final loss: {float(mets['loss']):.3f}")


if __name__ == "__main__":
    enable_persistent_cache()
    main()
