"""Production training launcher.

Single-host CPU runs execute really; on a TPU pod slice the same script runs
under the production mesh (sharding specs from launch/cells.py). The MPE
pipeline (search → sample → retrain → export) is the default recsys flow.

Examples:
    python -m repro.launch.train --arch wide-deep --steps 500 --reduced
    python -m repro.launch.train --arch dlrm-criteo --backbone dcn \
        --compressor mpe --steps 300 --reduced --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_arch
from repro.core.mpe import MPEConfig
from repro.core.pipeline import run_mpe_pipeline
from repro.data.synthetic import CTRSpec, SyntheticCTR
from repro.dist.mesh import init_distributed, parse_mesh_flag
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dlrm import DLRMConfig
from repro.train.loop import Trainer
from repro.train.optimizer import adam
from repro.zoo import dlrm_builder, wide_deep_builder


def _check_packed_lookup(res, fields, mesh, *, lookup_comms, bucket_capacity,
                         seed):
    """Post-train packed-lookup parity check under the training mesh.

    Runs the row-sharded lookup on the just-packed table through the
    selected comms path and asserts it is bit-exact against the
    single-device ``core.inference.packed_lookup`` reference, printing the
    deterministic a2a routing counters — the quickest way to see, on a real
    mesh, how the chosen ``--bucket-capacity`` routes this table's traffic.
    """
    import numpy as np

    from repro.core.inference import packed_lookup
    from repro.dist.shard import lookup_route_stats, sharded_packed_lookup

    table, meta = res["packed_table"], res["packed_meta"]
    rng = np.random.default_rng(seed)
    ids = jax.numpy.asarray(rng.integers(0, meta["n"], size=(512,)),
                            dtype=jax.numpy.int32)
    want = np.asarray(packed_lookup(table, meta, ids))
    got = np.asarray(sharded_packed_lookup(
        table, meta, ids, mesh=mesh, lookup_comms=lookup_comms,
        bucket_capacity=bucket_capacity))
    exact = bool(np.array_equal(want, got))
    line = f"[train] lookup check ({lookup_comms}): bit_exact={exact}"
    if lookup_comms == "a2a":
        stats = lookup_route_stats(table, meta, ids,
                                   n_shards=mesh.shape["model"],
                                   bucket_capacity=bucket_capacity)
        line += (f" capacity={stats['capacity']} routed={stats['routed']} "
                 f"bucketed={stats['bucketed']} spilled={stats['spilled']}")
    print(line)
    if not exact:
        raise SystemExit("[train] sharded packed lookup diverged from the "
                         "single-device reference")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-criteo")
    ap.add_argument("--backbone", default="dnn")
    ap.add_argument("--compressor", default="mpe",
                    help="mpe | plain | lsq | alpt | qr | pep | optfs")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--retrain-steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--lam", type=float, default=3e-5)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prefetch", action="store_true",
                    help="stage batches on device one step ahead of compute "
                         "(repro.cache.PrefetchPipeline); loss-identical to "
                         "the synchronous loop")
    ap.add_argument("--mesh", default=None,
                    help="'dp,mp', 'pod,dp,mp' or 'auto': run the train step "
                         "under shard_map on a (data, model) — or multi-pod "
                         "(pod, data, model) — device mesh: batch "
                         "data-parallel over the non-model axes, "
                         "embedding-table rows sharded over the model axis "
                         "with row-shard-local grad updates "
                         "(repro.dist.shard). Virtualize CPU devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--lookup-comms", choices=("psum", "a2a"), default="psum",
                    help="model-axis comms path for the post-train packed "
                         "lookup check under --mesh: 'psum' merges "
                         "dequantized partials, 'a2a' shuffles ids and "
                         "ships back packed words (repro.dist.shard; "
                         "bit-exact either way, route stats printed)")
    ap.add_argument("--bucket-capacity", type=int, default=None,
                    help="a2a ids per destination shard per batch slice "
                         "(default: full slice); overflow spills to psum")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host: coordinator host:port for "
                         "jax.distributed.initialize")
    ap.add_argument("--num-hosts", type=int, default=None,
                    help="multi-host: total process count")
    ap.add_argument("--host-id", type=int, default=None,
                    help="multi-host: this process's index in [0, num-hosts)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    init_distributed(coordinator=args.coordinator,
                     num_processes=args.num_hosts, process_id=args.host_id)
    mesh = parse_mesh_flag(args.mesh)
    if mesh is not None:
        print(f"[train] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    spec = get_arch(args.arch)
    if spec.family != "recsys":
        raise SystemExit("train.py drives the recsys flow; "
                         "use examples/ for lm/gnn end-to-end runs")

    if args.arch == "wide-deep":
        cfg = spec.make_config(args.reduced)
        fields = cfg.fields
        builder_fn = wide_deep_builder
    else:
        cfg = spec.make_config(args.reduced, backbone=args.backbone) \
            if args.arch == "dlrm-criteo" else spec.make_config(args.reduced)
        fields = cfg.fields
        builder_fn = dlrm_builder
        if not isinstance(cfg, DLRMConfig):
            raise SystemExit(f"{args.arch}: use examples/ for this arch")

    ds = SyntheticCTR(CTRSpec(field_vocabs=tuple(f.vocab for f in fields),
                              batch_size=args.batch, seed=args.seed))
    eval_batches = ds.eval_set(4)
    build = builder_fn(cfg, ds.expected_frequencies(), lam=args.lam,
                       eval_batches=eval_batches)

    if args.compressor == "mpe":
        res = run_mpe_pipeline(
            build, lambda s: ds.batch(s), key=jax.random.PRNGKey(args.seed),
            mpe_cfg=MPEConfig(lam=args.lam), optimizer=adam(args.lr),
            search_steps=args.steps,
            retrain_steps=args.retrain_steps or args.steps,
            eval_fn=build(jax.random.PRNGKey(args.seed), "plain", {})["eval_fn"],
            ckpt_dir=args.ckpt_dir, prefetch=args.prefetch, mesh=mesh)
        print(f"[train] MPE ratio={res['storage_ratio']:.4f} "
              f"avg_bits={res['avg_bits']:.2f} eval={res['eval']}")
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            _check_packed_lookup(res, fields, mesh,
                                 lookup_comms=args.lookup_comms,
                                 bucket_capacity=args.bucket_capacity,
                                 seed=args.seed)
        return

    comp_cfg = {"bits": 6} if args.compressor == "lsq" else \
               {"bits": 8} if args.compressor == "alpt" else \
               {"total_steps": args.steps} if args.compressor == "optfs" else {}
    bundle = build(jax.random.PRNGKey(args.seed), args.compressor, comp_cfg)
    from repro.core import get_compressor
    comp = get_compressor(args.compressor)
    post = None
    if args.compressor == "alpt":
        key_holder = {"k": jax.random.PRNGKey(args.seed + 1)}

        def post(params):
            key_holder["k"], sub = jax.random.split(key_holder["k"])
            emb = comp.post_update(params["embedding"], {}, comp_cfg, sub)
            return dict(params, embedding=emb)

    trainer = Trainer(bundle["loss_fn"], bundle["params"], bundle["buffers"],
                      bundle["state"], adam(args.lr), ckpt_dir=args.ckpt_dir,
                      post_update=post, mesh=mesh)
    trainer.restore()
    trainer.run(lambda s: ds.batch(s), args.steps, prefetch=args.prefetch)
    ev = bundle["eval_fn"](trainer.params, bundle["buffers"], trainer.state)
    r = comp.storage_ratio(trainer.params["embedding"],
                           bundle["buffers"]["embedding"], comp_cfg)
    print(f"[train] {args.compressor} ratio={r:.4f} eval={ev}")


if __name__ == "__main__":
    main()
