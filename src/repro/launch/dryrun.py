"""Multi-pod dry-run: ``.lower().compile()`` every (arch x shape x mesh)
cell on the production meshes and record memory / cost / collective-bytes
for the roofline analysis (EXPERIMENTS.md Sec. Dry-run / Sec. Roofline).

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all --skip-existing
    python -m repro.launch.dryrun --all --multi-pod
Results: one JSON per cell under --out-dir (default benchmarks/dryrun_results).
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config, supports_shape
from repro.distributed.sharding import rules_for_mesh
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.models import params as pm
from repro.roofline import analysis
from repro.training import optimizer as opt
from repro.training.train_step import make_train_step

#: The chip whose pods the dry run models: its roofline terms use this
#: kind's peaks (the compile itself runs on forced host devices).
TARGET_DEVICE_KIND = "TPU v5 lite"


def _sds(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )


def _tree_sds(spec_tree, mesh, rules):
    return _sds(pm.shape_tree(spec_tree),
                pm.sharding_tree(spec_tree, mesh, rules.resolve))


def model_flops_global(cfg, model, shape) -> float:
    """6ND (train) / 2ND (inference) with N = active non-embedding params
    (MoE expert tensors scaled by top_k/num_experts; unembed included)."""
    leaves = jax.tree_util.tree_flatten_with_path(
        model.specs(), is_leaf=pm.is_spec)[0]
    n = 0.0
    for path, p in leaves:
        name = jax.tree_util.keystr(path)
        size = float(np.prod(p.shape))
        if "embed" in name and "unembed" not in name:
            continue
        if (cfg.moe is not None and len(p.shape) >= 3
                and cfg.moe.num_experts in p.shape):
            size *= cfg.moe.top_k / cfg.moe.num_experts
        n += size
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per row


def build_lowerable(arch: str, shape_name: str, mesh, *,
                    microbatches: int = 1, cfg_overrides: dict | None = None,
                    robust_agg: bool = False):
    """Returns (fn, example_args) ready for jit(...).lower(*args)."""
    import dataclasses

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    rules = rules_for_mesh(mesh)
    if robust_agg:
        # DCF-PCA consensus aggregation: per-worker grads via shard_map
        # over DP; params must not be DP(FSDP)-sharded.
        from repro.distributed.grad_compress import CompressConfig
        from repro.training.train_step import make_robust_train_step

        assert shape.kind == "train"
        # Pure-DP cell: the measurement target is the gradient-aggregation
        # traffic (plain all-reduce vs consensus factorization); TP inside
        # the manual-DP shard_map trips an XLA:CPU bug (invalid opcode) at
        # 512 devices, so params stay replicated here.
        from repro.distributed.sharding import ShardingRules

        rules = ShardingRules(dp=rules.dp)
        params_sds = _tree_sds(model.specs(), mesh, rules)
        step = make_robust_train_step(model, opt.AdamWConfig(), mesh, rules,
                                      CompressConfig())
        opt_sds = opt.AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                               sharding=s.sharding),
                params_sds),
            v=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                               sharding=s.sharding),
                params_sds),
        )
        batch_sds = _tree_sds(model.batch_specs(shape), mesh, rules)
        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        fn = jax.jit(step, donate_argnums=(0, 1))
        return fn, (params_sds, opt_sds, batch_sds, key_sds)
    if shape.kind != "train":
        # Serving policy: replicate weights across the DP axes when a TP
        # shard fits comfortably (<= 4 GB/device) -- per-step param
        # all-gathers are pure waste for small models.  Huge models keep
        # ZeRO-style FSDP sharding (jamba-398B's TP shard alone is ~25 GB).
        import dataclasses

        import numpy as np
        param_bytes = sum(
            int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
            for p in jax.tree.leaves(model.specs(), is_leaf=pm.is_spec)
        )
        if param_bytes / 16 <= 4e9:  # TP_SIZE = 16
            rules = dataclasses.replace(rules, fsdp=None)

    params_sds = _tree_sds(model.specs(), mesh, rules)
    batch_sds = _tree_sds(model.batch_specs(shape), mesh, rules)

    if shape.kind == "train":
        step = make_train_step(model, opt.AdamWConfig(), rules,
                               microbatches=microbatches)
        opt_sds = opt.AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                               sharding=s.sharding),
                params_sds),
            v=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                               sharding=s.sharding),
                params_sds),
        )
        fn = jax.jit(step, donate_argnums=(0, 1))
        return fn, (params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        def prefill(params, batch):
            return model.prefill(params, batch, rules)

        fn = jax.jit(prefill)
        return fn, (params_sds, batch_sds)

    # decode
    cache_sds = _tree_sds(
        model.cache_specs(shape.global_batch, shape.seq_len), mesh, rules)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    def decode(params, tokens, caches, pos):
        return model.decode_step(params, tokens, caches, pos, rules)

    fn = jax.jit(decode, donate_argnums=(2,))
    return fn, (params_sds, batch_sds["tokens"], cache_sds, pos_sds)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, microbatches: int = 1,
             cfg_overrides: dict | None = None,
             variant: str = "", robust_agg: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    model = get_model(cfg)

    t0 = time.time()
    fn, args = build_lowerable(arch, shape_name, mesh,
                               microbatches=microbatches,
                               cfg_overrides=cfg_overrides,
                               robust_agg=robust_agg)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()

    roof = analysis.analyze(
        compiled, device_kind=TARGET_DEVICE_KIND, arch=arch,
        shape=shape_name, mesh_name=mesh_name, n_devices=mesh.size,
        model_flops_global=model_flops_global(cfg, model, shape),
    )
    rec = roof.to_dict()
    rec.update(
        t_lower_s=round(t_lower, 1), t_compile_s=round(t_compile, 1),
        microbatches=microbatches, variant=variant,
        cfg_overrides=cfg_overrides or {},
        memory_analysis=str(mem),
    )
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if microbatches != 1:
        tag += f"__mb{microbatches}"
    if variant:
        tag += f"__{variant}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out-dir", default="benchmarks/dryrun_results")
    ap.add_argument("--skip-existing", action="store_true")
    # Sec. Perf hillclimb levers (see EXPERIMENTS.md):
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert parallelism over the model axis")
    ap.add_argument("--bf16-norm-grad", action="store_true",
                    help="bf16 residual cotangent through norms")
    ap.add_argument("--remat", choices=("full", "dots", "none"), default=None)
    ap.add_argument("--seq-parallel", action="store_true",
                    help="Megatron-style sequence parallelism between blocks")
    ap.add_argument("--robust-agg", action="store_true",
                    help="DCF-PCA consensus gradient aggregation (paper "
                         "technique) in the lowered train step")
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--variant", default="",
                    help="tag appended to the result filename")
    args = ap.parse_args()

    overrides = {}
    if args.moe_ep:
        overrides["moe_ep"] = True
    if args.bf16_norm_grad:
        overrides["bf16_norm_grad"] = True
    if args.remat:
        overrides["remat"] = args.remat
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.q_chunk:
        overrides["q_chunk"] = args.q_chunk
    variant = args.variant or "".join(
        t for t, on in (("ep", args.moe_ep), ("bf16g", args.bf16_norm_grad),
                        ("sp", args.seq_parallel),
                        ("dcfagg", args.robust_agg),
                        (f"rm-{args.remat}", bool(args.remat)),
                        (f"qc{args.q_chunk}", bool(args.q_chunk)))
        if on)

    cells = []
    archs = ARCH_IDS if args.all else [args.arch]
    shapes = tuple(SHAPES) if args.all else [args.shape]
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for arch in archs:
        for shape in shapes:
            ok, why = supports_shape(get_config(arch), SHAPES[shape])
            if not ok:
                print(f"SKIP {arch} x {shape}: {why}")
                continue
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = []
    for arch, shape, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        tag = f"{arch}__{shape}__{mesh_name}"
        if args.microbatches != 1:
            tag += f"__mb{args.microbatches}"
        if variant:
            tag += f"__{variant}"
        path = os.path.join(args.out_dir, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"SKIP (exists) {tag}")
            continue
        print(f"=== {tag} ...", flush=True)
        try:
            rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out_dir,
                           microbatches=args.microbatches,
                           cfg_overrides=overrides or None, variant=variant,
                           robust_agg=args.robust_agg)
            print(
                f"    OK lower={rec['t_lower_s']}s compile={rec['t_compile_s']}s"
                f" | compute={rec['t_compute']*1e3:.2f}ms"
                f" memory={rec['t_memory']*1e3:.2f}ms"
                f" collective={rec['t_collective']*1e3:.2f}ms"
                f" -> {rec['bottleneck']}"
                f" | useful={rec['useful_flops_ratio']:.2f}"
                f" roofline_frac={rec['roofline_fraction']:.3f}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 -- report and continue
            failures.append((tag, repr(e)))
            print(f"    FAIL {e!r}")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        raise SystemExit(1)
    print("\nAll requested dry-run cells compiled.")


if __name__ == "__main__":
    main()
