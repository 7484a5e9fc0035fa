"""The paper's technique as a training feature: DCF-PCA consensus gradient
aggregation surviving a Byzantine (corrupted) data-parallel worker.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/robust_aggregation.py

Two short training runs on 8 DP workers where worker 3's gradient suffers
gross sparse corruption every step (5% of entries at +-1e4 -- bit-flip /
poisoned-shard scale):

* plain all-reduce: the corrupted mean saturates gradient clipping and
  training freezes near the initial loss;
* DCF-PCA consensus (rank-16 factors + error feedback; sparse S_i absorbs
  the corruption; small leaves combined by coordinate-wise median) keeps
  descending.

Only the consensus U and the mean V cross the wire -- 50x fewer bytes than
the all-reduce (benchmarks/robust_agg_dryrun.py).
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.distributed.grad_compress import CompressConfig, aggregate_leaf
from repro.distributed.sharding import ShardingRules
from repro.models import get_model
from repro.models import params as pm
from repro.training import optimizer as opt
from repro.training.data import SyntheticData
from repro.launch.mesh import make_compat_mesh
from repro.jax_cache import enable_persistent_cache

CORRUPT_WORKER = 3
CORRUPT_DENSITY = 0.05
CORRUPT_MAG = 1e4
CCFG = CompressConfig(rank=16, rounds=3, min_dim=32)


def make_step(model, mesh, mode, ocfg, rules):
    def per_worker(params, err, batch, key):
        (loss, _), grads = jax.value_and_grad(
            lambda pp, b: model.loss(pp, b, rules), has_aux=True)(
                params, batch)
        idx = jax.lax.axis_index("data")
        leaves, td = jax.tree.flatten(grads)
        ks = jax.random.split(key, len(leaves))

        def corrupt(g, k):
            k1, k2 = jax.random.split(k)
            mask = jax.random.bernoulli(k1, CORRUPT_DENSITY, g.shape)
            sign = jax.random.rademacher(k2, g.shape).astype(jnp.float32)
            noise = jnp.where(idx == CORRUPT_WORKER,
                              mask * sign * CORRUPT_MAG, 0.0)
            return g + noise.astype(g.dtype)

        grads = jax.tree.unflatten(
            td, [corrupt(g, k) for g, k in zip(leaves, ks)])

        if mode == "robust":
            # DCF-PCA consensus + error feedback (PowerSGD-style): the
            # per-worker compression residual re-enters next step.
            def one(g, e, k):
                ge = g.astype(jnp.float32) + e[0]
                agg = aggregate_leaf(ge, ("data",), CCFG, k)
                return agg.astype(g.dtype), (ge - agg)[None]

            leaves_g, td2 = jax.tree.flatten(grads)
            leaves_e = td2.flatten_up_to(err)
            ks2 = jax.random.split(jax.random.fold_in(key, 1), len(leaves_g))
            outs = [one(g, e, k)
                    for g, e, k in zip(leaves_g, leaves_e, ks2)]
            grads = td2.unflatten([o[0] for o in outs])
            err = td2.unflatten([o[1] for o in outs])
        else:
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, ("data",)), grads)
        return grads, err, jax.lax.pmean(loss, ("data",))

    def step(params, err, state, batch, key):
        pspecs = jax.tree.map(lambda _: P(), params)
        bspecs = jax.tree.map(
            lambda x: P(("data",), *(None,) * (x.ndim - 1)), batch)
        especs = jax.tree.map(lambda _: P("data"), err)
        grads, err, loss = jax.shard_map(
            per_worker, mesh=mesh,
            in_specs=(pspecs, especs, bspecs, P()),
            out_specs=(pspecs, especs, P()),
            axis_names=frozenset({"data"}),
            check_vma=False,
        )(params, err, batch, key)
        params, state, _ = opt.update(ocfg, grads, state, params)
        return params, err, state, loss

    return jax.jit(step)


def run(mode: str, steps=25):
    cfg = get_smoke_config("tinyllama-1.1b")
    model = get_model(cfg)
    n = jax.device_count()
    mesh = make_compat_mesh((n,), ("data",))
    ocfg = opt.AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=steps,
                           weight_decay=0.0)
    params = pm.materialize(model.specs(), jax.random.PRNGKey(0))
    state = opt.init(params)
    err = jax.tree.map(lambda p: jnp.zeros((n, *p.shape), jnp.float32),
                       params)
    data = SyntheticData(cfg, ShapeSpec("t", 64, 8, "train"))
    step = make_step(model, mesh, mode, ocfg, ShardingRules())
    losses = []
    with mesh:
        for i in range(steps):
            params, err, state, loss = step(
                params, err, state, data.batch_at(i),
                jax.random.fold_in(jax.random.PRNGKey(9), i))
            losses.append(float(loss))
    return losses


def main():
    print(f"devices: {jax.device_count()} (want 8: set XLA_FLAGS)")
    plain = run("plain")
    robust = run("robust")
    print(f"{'step':>5s} {'plain-allreduce':>16s} {'dcf-consensus':>14s}")
    for i in range(0, len(plain), 5):
        print(f"{i:5d} {plain[i]:16.3f} {robust[i]:14.3f}")
    print(f"final {plain[-1]:16.3f} {robust[-1]:14.3f}")
    assert robust[-1] < plain[-1] - 0.1, (
        "robust aggregation should keep learning under corruption")
    print("OK: consensus aggregation survives the Byzantine worker")


if __name__ == "__main__":
    enable_persistent_cache()
    main()
