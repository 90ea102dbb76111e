"""Sharded vs single-device step times + per-collective bytes → BENCH_shard.json.

Runs the four Pallas-kernel paths, the packed score cell and the shard_map
train step on a 1x1 mesh and on real multi-device meshes (1x4, 2x2 by
default — with ``--devices N`` the CPU backend is split into N virtual
devices before jax initializes; without it the visible devices are used
as they are), records p50
step wall-clock per mesh, and parses the compiled post-SPMD HLO of the
sharded lookup + train step with ``repro.launch.hlo_analysis`` to report the
per-collective byte counts the roofline consumes
(``python -m benchmarks.roofline --shard-bench BENCH_shard.json``).

On shared CI runners the absolute times are noisy (all virtual devices share
one CPU — sharded is *expected* to be slower there); the value of the
artifact is the trajectory and the collective byte counts, which are exact.

    PYTHONPATH=src python benchmarks/shard_bench.py --smoke
    PYTHONPATH=src python benchmarks/shard_bench.py --devices 4 --out BENCH_shard.json
"""
from __future__ import annotations

import argparse
import os
import sys


def _early_devices() -> int | None:
    """--devices must take effect before jax initializes its backend."""
    for i, a in enumerate(sys.argv):
        if a == "--devices" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return None


_N_DEV = _early_devices()
if _N_DEV is not None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_DEV}").strip()

import json  # noqa: E402
import platform  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import quantizer  # noqa: E402
from repro.core.inference import build_packed_table  # noqa: E402
from repro.core.mpe import MPEConfig  # noqa: E402
from repro.dist import shard  # noqa: E402
from repro.dist.mesh import host_mesh, make_device_mesh, use_mesh  # noqa: E402
from repro.launch.hlo_analysis import analyze  # noqa: E402

SMOKE = dict(n=2000, d=16, batch=256, bag_rows=1000, bag_batch=64, bag_l=8,
             attn=(2, 64, 4, 32), qat_rows=1024, iters=20,
             train_vocabs=(300, 200), train_batch=256, train_iters=10)
FULL = dict(n=20000, d=32, batch=1024, bag_rows=10000, bag_batch=256, bag_l=16,
            attn=(4, 128, 8, 64), qat_rows=8192, iters=50,
            train_vocabs=(2000, 1500), train_batch=1024, train_iters=20)


def _meshes():
    n = jax.device_count()
    out = [("1x1", host_mesh(n_data=1, n_model=1))]
    if n >= 4:
        out += [("1x4", make_device_mesh((1, 4), ("data", "model"))),
                ("2x2", make_device_mesh((2, 2), ("data", "model")))]
    elif n > 1:
        out += [(f"1x{n}", make_device_mesh((1, n), ("data", "model")))]
    return out


def _time_ms(fn, args, iters):
    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return round(float(np.percentile(times, 50)), 4)


def _collectives(jitted, *args) -> dict:
    """Per-collective byte counts of a compiled callable (loop-aware,
    per-device — see hlo_analysis)."""
    lowered = jitted.lower(*args)
    coll = analyze(lowered.compile().as_text())["collectives_per_device"]
    return {k: (v if isinstance(v, (int, float)) else dict(v))
            for k, v in coll.items()}


def bench_kernels(cfg: dict) -> dict:
    rng = np.random.default_rng(0)
    mcfg = MPEConfig()
    n, d = cfg["n"], cfg["d"]
    emb = rng.normal(size=(n, d)).astype(np.float32)
    fbits = rng.integers(0, len(mcfg.bits), size=n).astype(np.int32)
    alpha = (np.abs(rng.normal(size=len(mcfg.bits))) * 0.1 + 0.01).astype(np.float32)
    beta = (rng.normal(size=d) * 0.01).astype(np.float32)
    table, meta = build_packed_table(emb, fbits, alpha, beta, mcfg)
    ids = jnp.asarray(rng.integers(0, n, size=(cfg["batch"],)), jnp.int32)

    bag_tab = jnp.asarray(rng.normal(0, 1, (cfg["bag_rows"], d)), jnp.float32)
    bag_ids = jnp.asarray(rng.integers(0, cfg["bag_rows"],
                                       (cfg["bag_batch"], cfg["bag_l"])))
    bag_mask = jnp.ones((cfg["bag_batch"], cfg["bag_l"]), bool)

    b_, s, h, hd = cfg["attn"]
    q = jnp.asarray(rng.normal(0, 1, (b_, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b_, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b_, s, h, hd)), jnp.float32)

    bits = mcfg.bits
    rows = jnp.asarray(rng.normal(0, 3e-3, (cfg["qat_rows"], d)), jnp.float32)
    probs = jax.nn.softmax(
        jnp.asarray(rng.normal(0, 1, (cfg["qat_rows"], len(bits))),
                    jnp.float32), -1)
    qa = jnp.asarray([quantizer.init_alpha(3e-3, b) for b in bits])
    qb = jnp.asarray(rng.normal(0, 1e-4, (d,)), jnp.float32)

    kernels = {
        "mpe_lookup": (lambda t, i: shard.sharded_packed_lookup(t, meta, i),
                       (table, ids)),
        "embedding_bag": (lambda t, i, m: shard.sharded_embedding_bag(t, i, m),
                          (bag_tab, bag_ids, bag_mask)),
        "flash_attention": (
            lambda a, b2, c: shard.sharded_flash_attention(a, b2, c),
            (q, k, v)),
        "mpe_qat": (
            lambda r, p, a, b2: shard.sharded_mixed_expectation(r, p, a, b2,
                                                                bits),
            (rows, probs, qa, qb)),
    }

    out = {}
    for mesh_name, mesh in _meshes():
        with use_mesh(mesh):
            entry = {}
            for kname, (fn, args) in kernels.items():
                jitted = jax.jit(fn)
                rec = {"p50_ms": _time_ms(jitted, args, cfg["iters"])}
                if mesh.size > 1 and kname == "mpe_lookup":
                    rec["collectives"] = _collectives(jitted, *args)
                entry[kname] = rec
            out[mesh_name] = entry
        print(f"[shard_bench] kernels {mesh_name}: " +
              " ".join(f"{k}={v['p50_ms']}ms" for k, v in out[mesh_name].items()))
    return out


CROSS_BITS = {"b4": 4, "b8": 8, "b16": 16}
CROSS = dict(n=4096, d=16, batch=256)  # fixed: the crossover rows are gated


def bench_crossover(cfg: dict) -> dict:
    """psum-vs-a2a sweep: model-axis width × bucket capacity × bit-width.

    Every row records the measured p50 of both comms paths, the exact
    per-collective byte counts from the compiled HLO, and the deterministic
    routing counters of ``shard.lookup_route_stats`` — the counters, byte
    totals, compile counts and the ``a2a_fewer_bytes`` verdict are pure
    functions of this fixed config (``CROSS``, independent of --smoke), so
    ``bench-gate`` exact-diffs them against the checked-in baseline. The
    crossover itself: at d=16 a packed row is W=ceil(b·16/32) words, so a2a
    ships ~4·(ids + 2·W·batch) bytes against psum's 64·batch — below b≈16
    the id shuffle wins, above it the dense partial merge does.
    """
    rng = np.random.default_rng(7)
    n, d, batch = CROSS["n"], CROSS["d"], CROSS["batch"]
    emb = rng.normal(size=(n, d)).astype(np.float32)
    beta = (rng.normal(size=d) * 0.01).astype(np.float32)
    ids = jnp.asarray(rng.integers(0, n, size=(batch,)), jnp.int32)

    def _compiles(jitted) -> int:
        try:
            return int(jitted._cache_size())
        except Exception:  # noqa: BLE001 — internal API; absence → "compiled once"
            return 1

    out = {}
    for mp in (2, 4):
        if mp > jax.device_count():
            continue
        mesh = make_device_mesh((1, mp), ("data", "model"))
        rows = {}
        with use_mesh(mesh):
            for bname, b in CROSS_BITS.items():
                mcfg = MPEConfig(bits=(0, b))
                fbits = np.ones(n, np.int32)  # every feature at width b
                alpha = np.asarray(
                    [quantizer.init_alpha(0.1, bb) for bb in mcfg.bits],
                    np.float32)
                table, meta = build_packed_table(emb, fbits, alpha, beta, mcfg)
                slice_len = -(-batch // mp)
                caps = {"full": None, "half": max(1, slice_len // 2),
                        "quarter": max(1, slice_len // 4)}
                jp = jax.jit(lambda t, i, _m=meta:
                             shard.sharded_packed_lookup(t, _m, i))
                psum_ms = _time_ms(jp, (table, ids), cfg["iters"])
                pcoll = _collectives(jp, table, ids)
                want = np.asarray(jp(table, ids))
                per_bits = {}
                for cname, cap in caps.items():
                    ja = jax.jit(lambda t, i, _m=meta, _c=cap:
                                 shard.sharded_packed_lookup(
                                     t, _m, i, lookup_comms="a2a",
                                     bucket_capacity=_c))
                    a2a_ms = _time_ms(ja, (table, ids), cfg["iters"])
                    acoll = _collectives(ja, table, ids)
                    got = np.asarray(ja(table, ids))
                    rec = dict(shard.lookup_route_stats(
                        table, meta, ids, n_shards=mp, bucket_capacity=cap))
                    rec.update(
                        bit_width=b,
                        psum_p50_ms=psum_ms, a2a_p50_ms=a2a_ms,
                        psum_collectives=pcoll, a2a_collectives=acoll,
                        psum_collective_bytes=pcoll["total_bytes"],
                        a2a_collective_bytes=acoll["total_bytes"],
                        a2a_fewer_bytes=bool(acoll["total_bytes"]
                                             < pcoll["total_bytes"]),
                        bit_exact=bool(np.array_equal(want, got)),
                        psum_compiles=_compiles(jp),
                        a2a_compiles=_compiles(ja))
                    per_bits[cname] = rec
                rows[bname] = per_bits
                full = per_bits["full"]
                print(f"[shard_bench] crossover 1x{mp} {bname}: "
                      f"psum={full['psum_collective_bytes']:.0f}B "
                      f"a2a={full['a2a_collective_bytes']:.0f}B "
                      f"a2a_fewer={full['a2a_fewer_bytes']} "
                      f"exact={full['bit_exact']}")
        out[f"1x{mp}"] = rows
    return out


def bench_train_step(cfg: dict) -> dict:
    from repro.data.synthetic import CTRSpec, SyntheticCTR
    from repro.embeddings.table import FieldSpec
    from repro.models.dlrm import DLRMConfig
    from repro.train.loop import Trainer
    from repro.train.optimizer import adam
    from repro.zoo import dlrm_builder

    spec = CTRSpec(field_vocabs=cfg["train_vocabs"],
                   batch_size=cfg["train_batch"], seed=0)
    ds = SyntheticCTR(spec)
    fields = tuple(FieldSpec(f"f{i}", v)
                   for i, v in enumerate(spec.field_vocabs))
    base = DLRMConfig(fields=fields, d_embed=16, mlp_hidden=(64, 32),
                      backbone="dnn", use_batchnorm=False)
    build = dlrm_builder(base, ds.expected_frequencies())

    out = {}
    for mesh_name, mesh in _meshes():
        bundle = build(jax.random.PRNGKey(0), "plain", {})
        tr = Trainer(bundle["loss_fn"], bundle["params"], bundle["buffers"],
                     bundle["state"], adam(1e-3),
                     mesh=None if mesh.size <= 1 else mesh)
        t0 = time.time()
        tr.run(lambda s: ds.batch(s), cfg["train_iters"], log_every=0)
        ms = (time.time() - t0) / cfg["train_iters"] * 1e3
        rec = {"ms_per_step": round(ms, 3)}
        if mesh.size > 1:
            from repro.dist.shard import sharded_value_and_grad
            vag = sharded_value_and_grad(bundle["loss_fn"], mesh)
            batch = {k2: jnp.asarray(v2) for k2, v2 in ds.batch(0).items()}
            jitted = jax.jit(lambda p, bu, st, ba: vag(p, bu, st, ba, step=0))
            rec["collectives"] = _collectives(
                jitted, bundle["params"], bundle["buffers"], bundle["state"],
                batch)
        out[mesh_name] = rec
        print(f"[shard_bench] train {mesh_name}: {rec['ms_per_step']}ms/step")
    return out


def bench_serve_cell(cfg: dict) -> dict:
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine, train_packed_dlrm

    serve_cfg, params, state, buffers, spec, res = train_packed_dlrm(
        field_vocabs=cfg["train_vocabs"] + (500,), train_steps=20,
        train_batch=256, d_embed=16, mlp_hidden=(32,))
    req = SyntheticCTR(spec._replace(batch_size=128)).batch(10_000)["ids"]

    out = {}
    for mesh_name, mesh in _meshes():
        engine = build_engine(serve_cfg, params, state, buffers, p99_rows=128,
                              bulk_rows=512, lookup_split=False, mesh=mesh)
        engine.score(req)  # warm
        times = []
        for step in range(cfg["iters"]):
            t0 = time.perf_counter()
            engine.score(req)
            times.append((time.perf_counter() - t0) * 1e3)
        out[mesh_name] = {
            "score_p50_ms": round(float(np.percentile(times, 50)), 3),
            "compiles": engine.compile_count,
        }
        print(f"[shard_bench] serve {mesh_name}: "
              f"{out[mesh_name]['score_p50_ms']}ms "
              f"(compiles={engine.compile_count})")
    return out


def run(cfg: dict, crossover_only: bool = False) -> dict:
    out = {
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in cfg.items()},
        "env": {"jax": jax.__version__, "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "platform": platform.platform()},
    }
    if not crossover_only:
        out["kernels"] = bench_kernels(cfg)
        out["train"] = bench_train_step(cfg)
        out["serve"] = bench_serve_cell(cfg)
    out["crossover"] = bench_crossover(cfg)
    out["unix_time"] = int(time.time())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (the CI data point)")
    ap.add_argument("--devices", type=int, default=None,
                    help="split the CPU backend into this many virtual "
                         "devices (consumed before jax initializes; default: "
                         "the visible devices)")
    ap.add_argument("--crossover-only", action="store_true",
                    help="run just the psum-vs-a2a crossover sweep (the "
                         "bench-gate data point; its counters are "
                         "independent of --smoke)")
    ap.add_argument("--out", default=None,
                    help="output path (default benchmarks/artifacts/"
                         "BENCH_shard.json)")
    args = ap.parse_args(argv)

    out_path = args.out or os.path.join("benchmarks", "artifacts",
                                        "BENCH_shard.json")
    result = run(dict(SMOKE if args.smoke else FULL,
                      mode="smoke" if args.smoke else "full"),
                 crossover_only=args.crossover_only)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[shard_bench] wrote {out_path}")


if __name__ == "__main__":
    main()
