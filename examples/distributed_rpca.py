"""Distributed RPCA on a real device mesh (SPMD engine).

Run with several CPU devices to see the actual sharded execution:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_rpca.py

Each mesh shard along "data" is one of the paper's clients; the consensus
average of U is a single all-reduce per round; V_i and S_i never leave
their shard (the privacy property).  A second run row-shards the matrix
over a "model" axis as well (the beyond-paper 2-D extension), and a third
shows the elastic topology: a ragged column count that does not divide the
client count plus 60% per-round client participation (DESIGN.md Sec. 10).
"""
import jax

from repro.core import DCFConfig, dcf_pca_sharded, generate_problem, relative_error
from repro.launch.mesh import make_compat_mesh
from repro.jax_cache import enable_persistent_cache


def main():
    n_dev = jax.device_count()
    print(f"devices: {n_dev}")
    problem = generate_problem(jax.random.PRNGKey(1), 256, 320, rank=8,
                               sparsity=0.05)
    cfg = DCFConfig.tuned(rank=8)

    mesh = make_compat_mesh((n_dev,), ("data",))
    r = dcf_pca_sharded(problem.m_obs, cfg, mesh, data_axes=("data",))
    err = relative_error(r.l, r.s, problem.l0, problem.s0)
    print(f"1-D column-sharded ({n_dev} clients): err={float(err):.2e}")

    if n_dev >= 4 and n_dev % 2 == 0:
        mesh2 = make_compat_mesh((n_dev // 2, 2), ("data", "model"))
        r2 = dcf_pca_sharded(problem.m_obs, cfg, mesh2,
                             data_axes=("data",), model_axis="model")
        err2 = relative_error(r2.l, r2.s, problem.l0, problem.s0)
        print(f"2-D (rows x cols) sharded: err={float(err2):.2e}")

    # Elastic: ragged shards (n % E != 0 zero-pads behind a mask plane)
    # and Bernoulli(0.6) per-round participation with weighted consensus.
    ragged = generate_problem(jax.random.PRNGKey(2), 256, 301, rank=8,
                              sparsity=0.05)
    cfg_e = DCFConfig.elastic(rank=8, participation=0.6)
    r3 = dcf_pca_sharded(ragged.m_obs, cfg_e, mesh, participation=0.6)
    err3 = relative_error(r3.l, r3.s, ragged.l0, ragged.s0)
    print(f"elastic (n=301 over {n_dev} clients, 60% participation): "
          f"err={float(err3):.2e}")


if __name__ == "__main__":
    enable_persistent_cache()
    main()
