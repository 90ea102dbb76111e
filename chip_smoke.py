"""Smoke run of the MPE train → pack → serve path on a TPU.

One chip (the default): the paper's own ``dlrm-criteo`` configuration at
full width (39 fields, ~34M rows, d=16, MLP 1024-512-256, widths {0..6})
runs a few MPE search and retrain steps on seeded synthetic CTR data, packs
the sampled-width table, and answers score requests through
``repro.serve.Engine``. For the ids just served, the lookups are checked
against a host unpack + dequant and the probabilities against the model run
on the CPU backend.

Four chips (``--chips 4``): only the sharded serve path. A seeded packed
``dlrm-criteo`` table is served on a ``host_mesh(1, 4)`` engine with the
row-sharded lookup, once merging with ``psum`` and once through the
capacity-bucketed all-to-all at a capacity that forces spill. Each sharded
lookup must match the one-device lookup within 1 ulp, and each engine's
logits the one-device engine's within ``SHARD_LOGIT_RTOL``; how many values
differ at all is printed.

    python chip_smoke.py
    python chip_smoke.py --chips 4

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed. Without a TPU the script exits
non-zero before doing any work. The persistent compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: |p_chip - p_cpu| bound on served probabilities. TPU f32 matmuls run one
#: bf16 pass by default: each operand keeps 8 significant bits (relative
#: error 2^-9), so the four matmuls of the 624-1024-512-256-1 tower carry
#: the logit to within ~1% of its magnitude, and dp/dz <= 1/4.
PROB_ATOL = 1e-2

#: |logit_sharded - logit_one_device| bound, relative to the largest
#: one-device logit. The sharded and one-device score programs compute the
#: same tower on the same embeddings, but the compiler tiles each program's
#: f32 dot accumulations on its own. Reordering a sum of K <= 1024 terms
#: moves it by ~sqrt(K) * 2^-24 (2e-6) of its terms' magnitude typically,
#: K * 2^-24 (6e-5) at worst. A wrong or missing embedding row moves a
#: logit by percents, far above this bound.
SHARD_LOGIT_RTOL = 1e-5


class CompileMonitor:
    """Seconds spent tracing, lowering and compiling, and persistent-cache
    hits and misses, from jax's own monitoring events, while the monitor is
    open (a context manager; the listeners go away on exit)."""

    DURATION_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.DURATION_EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.cache_hits, self.cache_misses

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def _compile_delta(monitor, before) -> dict:
    s, h, m = monitor.snapshot()
    return {"compile_s": s - before[0], "cache_hits": h - before[1],
            "cache_misses": m - before[2]}


# ---------------------------------------------------------------------------
# phase 1: train → pack
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch: int = 2048, steps: int = 3, seed: int = 0,
                monitor=None, log=print):
    """A few MPE search steps and as many retrain steps on seeded synthetic
    data, then the packed export (``launch/serve.train_packed_dlrm``).
    Returns its ``(serve cfg, params, state, buffers, spec, result)``."""
    from repro.launch.serve import train_packed_dlrm

    before = monitor.snapshot() if monitor else None
    t0 = time.perf_counter()
    trained = train_packed_dlrm(
        field_vocabs=tuple(f.vocab for f in cfg.fields), train_steps=steps,
        train_batch=batch, d_embed=cfg.d_embed, mlp_hidden=cfg.mlp_hidden,
        seed=seed, log_every=1)
    phase_s = time.perf_counter() - t0
    res = trained[-1]
    for phase in ("search", "retrain"):
        hist = res["history"][phase]
        if len(hist) != steps:
            raise RuntimeError(f"{phase}: {len(hist)} logged steps, "
                               f"expected {steps}")
        for h in hist:
            log(f"[train] {phase} step {h['step']} loss {h['loss']:.6f} "
                f"grad_norm {h['grad_norm']:.4f} wall_s {h['wall_s']:.3f}")
            if not (np.isfinite(h["loss"]) and np.isfinite(h["grad_norm"])):
                raise RuntimeError(f"{phase} step {h['step']}: non-finite "
                                   f"loss {h['loss']}")
            if h["skipped"]:
                raise RuntimeError(f"{phase} step {h['step']}: update "
                                   f"skipped by the NaN guard")
        steady = [h["wall_s"] for h in hist[1:]]
        if steady:
            log(f"[train] {phase} steady step_s mean {np.mean(steady):.4f} "
                f"over {len(steady)} steps (first step, compile included: "
                f"{hist[0]['wall_s']:.3f} s)")
    log(f"[train] sampled avg_bits {res['avg_bits']:.4f} storage_ratio "
        f"{res['storage_ratio']:.5f} packed_bytes {res['packed_bytes']}")
    if monitor:
        delta = _compile_delta(monitor, before)
        log(f"[train] phase_s {phase_s:.2f} compile_s "
            f"{delta['compile_s']:.2f} cache_hits {delta['cache_hits']} "
            f"cache_misses {delta['cache_misses']}")
    return trained


# ---------------------------------------------------------------------------
# phase 2: serve, checked against the CPU backend
# ---------------------------------------------------------------------------

def _cpu_logits(serve_cfg, params, state, buffers, ids) -> np.ndarray:
    """The DLRM forward over the packed table for ``ids`` on the CPU
    backend."""
    import jax
    from repro.models.dlrm import DLRM

    cpu = jax.devices("cpu")[0]
    p, st, bu, x = jax.device_put(
        (params, state, buffers, np.asarray(ids, np.int32)), cpu)
    return np.asarray(jax.jit(lambda p_, st_, bu_, i: DLRM.apply(
        p_, bu_, st_, {"ids": i}, serve_cfg, train=False)[0])(p, st, bu, x))


def _lookup_references(table, meta, gids) -> tuple[np.ndarray, np.ndarray]:
    """The packed lookup of global ids ``gids``, unpacked on the CPU backend
    and dequantized on the host in the two roundings of ``alpha * code +
    beta`` a backend may use: one fused multiply-add (one rounding) and a
    multiply then an add (two). The integer unpack is exact either way;
    under cancellation the two roundings differ by more than 1 ulp, so each
    element is held to the nearer of the two."""
    import jax
    from repro.core import packing

    bits, d = meta["bits"], meta["d"]
    gids = np.asarray(gids).reshape(-1)
    widx = np.asarray(table["width_idx"])[gids]
    lidx = np.asarray(table["local_idx"])[gids]
    alpha = np.asarray(table["alpha"], np.float32)
    beta = np.asarray(table["beta"], np.float32)
    fused = np.zeros((gids.size, d), np.float32)
    split = np.zeros((gids.size, d), np.float32)
    for i, b in enumerate(bits):
        sel = widx == i
        if b == 0 or not sel.any():
            continue
        words = np.asarray(table["subtables"][f"b{b}"])[lidx[sel]]
        with jax.default_device(jax.devices("cpu")[0]):
            codes = np.asarray(packing.unpack_codes(words, b, d), np.float32)
        fused[sel] = (np.float64(alpha[i]) * codes
                      + beta.astype(np.float64)).astype(np.float32)
        split[sel] = alpha[i] * codes + beta
    return fused, split


def _ulp_distance(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    # map the sign-magnitude float order onto a monotone integer line
    ia = np.where(ia < 0, -(ia & 0x7FFFFFFF), ia)
    ib = np.where(ib < 0, -(ib & 0x7FFFFFFF), ib)
    return np.abs(ia - ib)


def _served_lookup(engine, shape: str, ids) -> np.ndarray:
    """Embeddings from the engine's own lookup executable for ``shape``."""
    import jax
    from repro.serve.batcher import RequestBatcher

    reg = next(r for r in engine.registered_cells().values()
               if r.celldef.shape == f"{shape}.lookup")
    padded, _ = RequestBatcher.pad(np.asarray(ids, np.int32),
                                   reg.celldef.batch)
    x = jax.device_put(padded, reg.cell.in_shardings[len(reg.bound)])
    return np.asarray(reg.cell.compiled(*reg.bound, x))[:len(ids)]


def serve_phase(trained, *, p99_rows: int = 512, bulk_rows: int = 4096,
                request_rows: int = 300, n_requests: int = 8,
                bulk_request_rows: int = 16384, seed: int = 0,
                monitor=None, log=print) -> dict:
    """Score requests through ``Engine.score`` (submit → drain → poll, raises
    on a failed request): ``n_requests`` of ``request_rows`` rows on
    ``serve_p99`` and one bulk request, after one warm-up request per cell.
    Checks zero compiles after warm-up, lookups within 1 ulp of the host
    reference and probabilities within ``PROB_ATOL`` of the CPU backend."""
    from repro.data.synthetic import SyntheticCTR
    from repro.launch.serve import build_engine

    serve_cfg, params, state, buffers, spec, _ = trained
    d = serve_cfg.comp_cfg["d"]
    before = monitor.snapshot() if monitor else None
    t0 = time.perf_counter()
    engine = build_engine(serve_cfg, params, state, buffers,
                          p99_rows=p99_rows, bulk_rows=bulk_rows)
    log(f"[serve] registered {dict(sorted(engine.registered_shapes.items()))}"
        f" compiles {engine.compile_count} in "
        f"{time.perf_counter() - t0:.2f} s")

    small = SyntheticCTR(spec._replace(batch_size=request_rows, seed=seed))
    large = SyntheticCTR(spec._replace(batch_size=bulk_request_rows,
                                       seed=seed))
    # warm-up: exactly one chunk per cell
    engine.score(small.batch(10_000)["ids"])
    engine.score(large.batch(20_000)["ids"][:bulk_rows])
    warm = engine.compile_count

    reqs = [small.batch(30_000 + i)["ids"] for i in range(n_requests)]
    bulk = large.batch(40_000)["ids"]
    t1 = time.perf_counter()
    probs = [engine.score(ids) for ids in reqs]
    bulk_probs = engine.score(bulk)
    serve_s = time.perf_counter() - t1
    if engine.compile_count != warm:
        raise RuntimeError(f"{engine.compile_count - warm} compiles after "
                           f"warm-up")
    for cell, s in engine.summary(skip_warmup=1).items():
        log(f"[serve] {cell} p50_ms {s['p50_ms']:.4f} p99_ms "
            f"{s['p99_ms']:.4f} lookup_p50_ms {s.get('lookup_p50_ms', 0):.4f}"
            f" chunks {s['count']} (first is warm-up, not counted)")
    log(f"[serve] {n_requests}x{request_rows} rows + 1x{bulk_request_rows} "
        f"rows in {serve_s:.3f} s; compiles after warm-up "
        f"{engine.compile_count - warm}")

    # correctness against the CPU backend, for the ids just served
    emb_chip = _served_lookup(engine, "serve_p99", reqs[0]).reshape(-1, d)
    meta = {k: serve_cfg.comp_cfg[k] for k in ("bits", "d", "n")}
    gids = reqs[0] + np.asarray(buffers["offsets"])[None, :]
    fused, split = _lookup_references(params["embedding"], meta, gids)
    ulps = np.minimum(_ulp_distance(emb_chip, fused),
                      _ulp_distance(emb_chip, split))
    ulp = int(ulps.max())
    n_fused = int(np.count_nonzero(emb_chip == fused))
    if ulp > 1:
        raise RuntimeError(f"served lookup is {ulp} ulp from the host "
                           f"reference")
    all_ids = np.concatenate(reqs + [bulk])
    got = np.concatenate(probs + [bulk_probs])
    logits_ref = _cpu_logits(serve_cfg, params, state, buffers, all_ids)
    want = 1.0 / (1.0 + np.exp(-logits_ref))
    err = float(np.max(np.abs(got - want)))
    if not np.all(np.isfinite(got)) or got.shape != (len(all_ids),):
        raise RuntimeError(f"served probabilities malformed: {got.shape}")
    log(f"[serve] lookup vs host: max_ulp {ulp} over {emb_chip.size} "
        f"values ({n_fused} equal to the fused dequant, "
        f"{int(np.count_nonzero(emb_chip == split))} to the unfused); "
        f"probs vs cpu: max_abs_err {err:.3e} (atol {PROB_ATOL}) over "
        f"{got.size} rows")
    if err > PROB_ATOL:
        raise RuntimeError(f"served probabilities differ from the CPU "
                           f"reference by {err:.3e} > {PROB_ATOL}")
    if monitor:
        delta = _compile_delta(monitor, before)
        log(f"[serve] phase_s {time.perf_counter() - t0:.2f} compile_s "
            f"{delta['compile_s']:.2f} cache_hits {delta['cache_hits']} "
            f"cache_misses {delta['cache_misses']}")
    return {"max_ulp": ulp, "max_prob_err": err}


# ---------------------------------------------------------------------------
# --chips 4: the sharded serve path
# ---------------------------------------------------------------------------

def seeded_packed_model(cfg, *, seed: int = 0):
    """A packed table from seeded weights and a seeded width assignment (no
    search), plus the DLRM tower: ``(serve cfg, params, state, buffers,
    spec)`` as ``build_engine`` takes them."""
    import jax
    from repro.core.mpe import MPEConfig
    from repro.data.synthetic import CTRSpec, SyntheticCTR
    from repro.models.dlrm import DLRM

    spec = CTRSpec(field_vocabs=tuple(f.vocab for f in cfg.fields),
                   seed=seed)
    freqs = SyntheticCTR(spec).expected_frequencies()
    params, buffers, state = DLRM.init(
        jax.random.PRNGKey(seed),
        cfg._replace(compressor="packed", comp_cfg=MPEConfig()._asdict()),
        freqs=freqs)
    meta = buffers["embedding"]["meta"]
    serve_cfg = cfg._replace(compressor="packed",
                             comp_cfg={k: meta[k] for k in ("bits", "d", "n")})
    return serve_cfg, params, state, dict(buffers, embedding={}), spec


def sharded_serve_phase(cfg, *, n_model: int = 4, p99_rows: int = 512,
                        bulk_rows: int = 4096, request_rows: int = 300,
                        n_requests: int = 4, bulk_request_rows: int = 8192,
                        seed: int = 0, log=print) -> dict:
    """Serve one seeded packed table on one device and on a
    ``host_mesh(1, n_model)`` engine, whose lookups run sharded — psum, then
    a2a at a bucket capacity that forces spill. The sharded lookup of the
    first ``serve_p99`` chunk must match the one-device lookup within 1 ulp
    and the served logits within ``SHARD_LOGIT_RTOL``; the counts of values
    that differ at all are returned per merge."""
    import jax
    from repro.core.inference import packed_lookup
    from repro.data.synthetic import SyntheticCTR
    from repro.dist.mesh import host_mesh
    from repro.dist.shard import lookup_route_stats, sharded_packed_lookup
    from repro.dist.sharding import packed_table_pspecs, tree_named_shardings
    from repro.launch.serve import build_engine
    from repro.serve.batcher import RequestBatcher

    serve_cfg, params, state, buffers, spec = seeded_packed_model(
        cfg, seed=seed)
    ds = SyntheticCTR(spec._replace(batch_size=request_rows))
    reqs = [ds.batch(50_000 + i)["ids"] for i in range(n_requests)]
    reqs.append(SyntheticCTR(spec._replace(batch_size=bulk_request_rows))
                .batch(60_000)["ids"])

    def serve(**kw):
        engine = build_engine(serve_cfg, params, state, buffers,
                              p99_rows=p99_rows, bulk_rows=bulk_rows,
                              lookup_split=False, **kw)
        out = [engine.score(ids, return_logits=True) for ids in reqs]
        return engine, np.concatenate(out)

    t0 = time.perf_counter()
    _, want = serve(mesh=host_mesh(n_data=1, n_model=1))
    log(f"[shard] one-device reference: {want.size} rows in "
        f"{time.perf_counter() - t0:.2f} s")
    if not np.all(np.isfinite(want)):
        raise RuntimeError("one-device reference logits are not finite")
    # a capacity of 1/8 of a p99 slice: every owner's bucket overflows
    slice_len = -(-p99_rows * len(cfg.fields) // n_model)
    capacity = max(1, slice_len // 8)
    padded, _ = RequestBatcher.pad(np.asarray(reqs[0], np.int32), p99_rows)
    gids = padded + np.asarray(buffers["offsets"])[None, :]
    route = lookup_route_stats(params["embedding"], serve_cfg.comp_cfg, gids,
                               n_shards=n_model, bucket_capacity=capacity)
    if route["spilled"] == 0:
        raise RuntimeError(f"capacity {capacity} forced no spill: {route}")
    table, meta = params["embedding"], {
        k: serve_cfg.comp_cfg[k] for k in ("bits", "d", "n")}
    emb_want = np.asarray(jax.jit(
        lambda t, g: packed_lookup(t, meta, g))(table, gids))
    logit_tol = SHARD_LOGIT_RTOL * float(np.max(np.abs(want)))
    out, faults = {}, []
    for comms, cap in (("psum", None), ("a2a", capacity)):
        mesh = host_mesh(n_data=1, n_model=n_model)
        t0 = time.perf_counter()
        engine, got = serve(mesh=mesh, lookup_comms=comms,
                            bucket_capacity=cap)
        placed = jax.device_put(table, tree_named_shardings(
            mesh, packed_table_pspecs(table)))
        emb = np.asarray(jax.jit(lambda t, g: sharded_packed_lookup(
            t, meta, g, mesh=mesh, lookup_comms=comms,
            bucket_capacity=cap))(placed, gids))
        res = {"lookup_differ": int(np.count_nonzero(emb != emb_want)),
               "lookup_max_ulp": int(_ulp_distance(emb, emb_want).max()),
               "logit_rows_differ": int(np.count_nonzero(got != want)),
               "max_logit_err": float(np.max(np.abs(got - want)))}
        log(f"[shard] {comms} capacity {cap}: {got.size} rows in "
            f"{time.perf_counter() - t0:.2f} s, compiles "
            f"{engine.compile_count}; lookup {res['lookup_differ']} of "
            f"{emb.size} values differ from one device (max "
            f"{res['lookup_max_ulp']} ulp); logits {res['logit_rows_differ']}"
            f" of {got.size} rows differ (max {res['max_logit_err']:.3e}, "
            f"tol {logit_tol:.3e})")
        if res["lookup_max_ulp"] > 1:
            faults.append(f"{comms}: lookup {res['lookup_max_ulp']} ulp from "
                          f"one device")
        if not np.all(np.isfinite(got)):
            faults.append(f"{comms}: non-finite logits")
        elif res["max_logit_err"] > logit_tol:
            faults.append(f"{comms}: logits {res['max_logit_err']:.3e} from "
                          f"one device > {logit_tol:.3e}")
        out[comms] = res
    log(f"[shard] a2a route of the first serve_p99 chunk: {route}")
    if faults:
        raise RuntimeError("; ".join(faults))
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serve path on four chips")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro next to this script in {HERE}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devices)} device(s))",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[device] platform {dev.platform} kind {dev.device_kind} count "
          f"{len(devices)} jax {jax.__version__}")
    print(f"[device] compile cache {cache_dir}")

    from repro.configs.dlrm_criteo import make_config
    cfg = make_config(reduced=False)
    print(f"[config] dlrm-criteo fields {len(cfg.fields)} rows "
          f"{sum(f.vocab for f in cfg.fields)} d {cfg.d_embed} mlp "
          f"{cfg.mlp_hidden} backbone {cfg.backbone}")
    with CompileMonitor() as monitor:
        if args.chips == 4:
            sharded_serve_phase(cfg)
            for d in devices[:4]:
                print(f"[memory] {d} peak_bytes_in_use {_peak_bytes(d)}")
        else:
            trained = train_phase(cfg, monitor=monitor)
            print(f"[memory] after train peak_bytes_in_use "
                  f"{_peak_bytes(dev)}")
            serve_phase(trained, monitor=monitor)
            print(f"[memory] after serve peak_bytes_in_use "
                  f"{_peak_bytes(dev)}")
        s, h, m = monitor.snapshot()
    print(f"[total] compile_s {s:.2f} cache_hits {h} cache_misses {m}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
