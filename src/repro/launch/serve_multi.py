"""Multi-tenant serving driver: heterogeneous adapter batch, one decode loop.

Spins up a :class:`repro.serving.MultiTenantEngine`, registers N tenants
(distinct random λ checkpoints; tenant 0 is the base model, slot 0), then
serves one request per tenant — all lanes decode in a single shared batch
with per-lane λ gathered by adapter-slot id.  Afterwards each tenant's
output is re-derived through the classic single-adapter deployment
(λ merged into the weights, launch/serve.py-style) and compared
token-for-token and logit-for-logit.

Every decode-capable family serves through the same loop (LaneState
protocol): ``--arch smollm-135m`` (dense attention), ``--arch
jamba-1.5-large-398b`` (hybrid: paged attention + dense Mamba state),
``--arch xlstm-125m`` (pure recurrent; no KV to page, so ``--layout
paged`` is rejected — ``--layout auto``, the default, picks the dense
oracle layout for it).  CLI flags map 1:1 onto
:class:`repro.serving.EngineConfig` fields: ``--layout`` / ``--block-size``
/ ``--n-blocks`` / ``--share-prefix`` / ``--watermark`` /
``--prefill-chunk`` configure the paged layout; ``--quantum`` (time-slice
fairness via lane-state snapshots) needs the dense oracle layout and
shines for recurrent families whose per-lane state is O(1);
``--speculate-k`` / ``--draft-lam-rank`` turn on speculative decoding via
the slot-0 base drafter (attention-only families, token-identical output);
``--base-dtype int8|fp8`` streams the frozen base quantized
per-output-channel with dequant in the kernel epilogue (λ/B/A stay full
precision); ``--shard-ba`` shards the shared QR factors over their rank
dim (bit-identical exact all_gather reassembly).

    PYTHONPATH=src python -m repro.launch.serve_multi --reduced --tenants 4
    PYTHONPATH=src python -m repro.launch.serve_multi --reduced \\
        --arch xlstm-125m --stream --quantum 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.obs import write_metrics
from repro.runtime.compile_cache import use_compile_cache
from repro.serving import (
    BASE_TENANT,
    EngineConfig,
    MultiTenantEngine,
    Router,
    base_lambda,
    build_replicas,
    random_lambda,
    reference_decode,
)


def logit_tolerance(dtype: str, base_dtype: str) -> float:
    """Largest |Δlogit| the engine may show against the merged-weight
    reference (``reference_decode``).

    * float32, bf16 base: 1e-3.  Both paths are fp32; they differ only in
      association — the engine adds ((x·B)·λ)·A to x·W, the reference
      multiplies x by W + (B·λ)·A.
    * quantized base: 5e-2.  The merged reference dequantizes the same
      {q, scale} dicts, but the engine contracts q in fp32 and scales in
      the epilogue while the merge scales q element-wise first — a ~1e-2
      logit split at reduced scale.
    * bfloat16: 0.25.  Every activation is rounded to 8 significant bits,
      the reference also rounds the merged W + (B·λ)·A to bf16, and the
      engine's kernel accumulates the adapter term in fp32; the gap grows
      over the residual stream.  Measured: 0.02–0.12 on XLA-CPU (d_model
      576 at 2 and 8 layers, d_model 96 at 30 layers) and 0.08–0.11 on a
      TPU v5e at smollm-135m's full width, against logits of std ≈ 1.0 —
      so 0.25 leaves 2x headroom, while serving a tenant the base λ
      instead of its own moves its logits by 0.7–0.8 (XLA-CPU, 8 layers).
    """
    if dtype == "bfloat16":
        return 0.25
    return 1e-3 if base_dtype == "bf16" else 5e-2


def compare_to_reference(tokens, logits, ref_tokens, ref_logits, tol):
    """Check one request against its merged-weight reference decode.

    Logits are compared while both decodes share their context: through
    the first position whose greedy tokens differ.  That position is a
    mismatch unless the reference's top-2 margin there is below ``tol``: a
    near-tie that rounding noise within the logit bar may flip.  After it
    the two decodes continue from different tokens and are not compared.
    Returns ``(ok, worst |Δlogit|, positions compared, the reference's
    top-2 margin at the split or None)``.
    """
    logits, ref_logits = np.asarray(logits), np.asarray(ref_logits)
    n = len(ref_tokens)
    split = next(
        (i for i, (a, b) in enumerate(zip(tokens, ref_tokens)) if a != b), n
    )
    upto = min(split + 1, n, len(logits))
    err = float(np.abs(logits[:upto] - ref_logits[:upto]).max())
    ok = err < tol and len(tokens) == n
    margin = None
    if split < n:
        top2 = np.sort(ref_logits[split])[-2:]
        margin = float(top2[1] - top2[0])
        ok = ok and margin < tol
    return ok, err, upto, margin


def tenant_lambdas(params, n_tenants: int, seed: int, lam_scale: float):
    """Tenant 0 is the base model (λ ≡ 0); the rest get distinct random λ."""
    lams = {BASE_TENANT: base_lambda(params)}
    for i in range(1, n_tenants):
        lams[f"tenant{i}"] = random_lambda(
            jax.random.PRNGKey(seed + 1000 + i), params, lam_scale
        )
    return lams


def tenant_prompts(tenants, vocab_size: int, prompt_len: int, seed: int):
    """One random prompt per tenant, in tenant order."""
    rng = np.random.default_rng(seed)
    return {
        t: rng.integers(2, vocab_size, size=prompt_len).astype(np.int32)
        for t in tenants
    }


def verify_tenants(cfg, params, lams, served, gen_len: int, max_len: int,
                   tol: float) -> float:
    """Check every served ``(tenant, prompt, tokens, logits)`` against its
    merged-weight reference (:func:`compare_to_reference`); exits on the
    first mismatch, returns the worst |Δlogit|."""
    worst = 0.0
    for tenant, prompt, tokens, logits in served:
        ref_toks, ref_logits = reference_decode(
            cfg, params, lams[tenant], prompt, gen_len, max_len
        )
        ok, err, n, margin = compare_to_reference(
            tokens, logits, ref_toks, ref_logits, tol
        )
        worst = max(worst, err)
        split = "" if margin is None else (
            f", split at token {n - 1} (reference top-2 margin {margin:.3g})"
        )
        print(f"[serve_multi] verify {tenant}: tokens {'OK' if ok else 'MISMATCH'} "
              f"max|Δlogits|={err:.2e} over {n}/{gen_len} positions{split}")
        if not ok:
            raise SystemExit(f"tenant {tenant} diverged from merged-weight reference")
    return worst


def configure(argv=None):
    """Parse the driver's flags into ``(args, model config, EngineConfig)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--lam-scale", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--layout", default="auto", choices=["auto", "paged", "oracle_dense"],
        help="KV-cache layout (EngineConfig.layout): 'paged' = block pool + "
        "per-lane block tables (the serving layout), 'oracle_dense' = the "
        "dense (lanes, max_len) reference region, 'auto' = paged whenever "
        "the family has attention layers to page (default)",
    )
    ap.add_argument(
        "--paged", action="store_true",
        help="deprecated alias of --layout paged",
    )
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument(
        "--n-blocks", type=int, default=None,
        help="KV pool size (default: dense-equivalent capacity + trash block)",
    )
    ap.add_argument(
        "--share-prefix", action="store_true",
        help="copy-on-write prefix sharing (paged layouts): requests "
        "repeating a prompt prefix reuse its resident KV blocks",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="TOKENS",
        help="chunked prefill (paged layouts): split long prompts into "
        "chunks of this many tokens, processed interleaved with resident "
        "lanes' decode so TBT stays bounded (must be a multiple of "
        "--block-size; default: monolithic admission prefill)",
    )
    ap.add_argument(
        "--watermark", type=int, default=0,
        help="free blocks admission keeps in reserve as decode-growth "
        "headroom (reduces mid-decode preemptions)",
    )
    ap.add_argument(
        "--replicas", type=int, default=1, metavar="N",
        help="serve through N engine replicas behind the adapter-locality "
        "router (serving/router.py): requests place by consistent hash of "
        "the tenant's λ digest with load-aware spillover, prefix-cache "
        "entries ship between replicas on miss (1 = plain single engine)",
    )
    ap.add_argument(
        "--disaggregate", action="store_true",
        help="prefill/decode disaggregation (needs --replicas >= 2): "
        "replica 0 runs chunked prefill only and streams committed blocks "
        "+ first-token logits to the decode replicas, which splice them "
        "into lanes with zero prompt recompute",
    )
    ap.add_argument(
        "--cold-path", default=None, metavar="PATH",
        help="back the λ cold tier with an mmap'd file at PATH (catalog "
        "JSON rides alongside) so the spilled tenant catalog survives a "
        "restart; needs --cold-slots > 0 (with --replicas N, replica i "
        "uses PATH.ri)",
    )
    ap.add_argument(
        "--cold-slots", type=int, default=0,
        help="host cold-tier capacity (tenants): λ evicted from the hot "
        "device slots spills to host arrays and is promoted back on "
        "admission, so tenant capacity is bounded by host RAM (0 disables)",
    )
    ap.add_argument(
        "--base-dtype", default="bf16", choices=["bf16", "int8", "fp8"],
        help="frozen-base weight dtype: int8/fp8 quantize every adapted "
        "base projection per-output-channel at engine construction and "
        "dequantize in the kernel epilogue — λ/B/A stay full precision "
        "(core/quantize.py)",
    )
    ap.add_argument(
        "--shard-ba", action="store_true",
        help="shard the shared QR factors B/A over their rank dim along a "
        "1-D 'model' mesh spanning all local devices (bit-identical to "
        "replicated — exact all_gather reassembly; try on CPU with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--shard-lam", action="store_true",
        help="shard the packed λ slot tables over a 1-D 'model' mesh "
        "spanning all local devices (bit-identical to replicated; try on "
        "CPU with XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--speculate-k", type=int, default=0, metavar="K",
        help="speculative decoding: draft K tokens per lane per step with "
        "the free slot-0 base drafter (λ ≡ 0 — same weights, same KV "
        "blocks), batch-verify under the full multi-λ view, accept the "
        "longest matching greedy prefix (token-identical output, up to K+1 "
        "tokens per host round-trip; 0 disables)",
    )
    ap.add_argument(
        "--draft-lam-rank", type=int, default=None, metavar="R",
        help="drafter variant: keep only the top-R |λ| coefficients per "
        "tenant slot instead of dropping the adapter entirely (needs "
        "--speculate-k >= 1; default: λ ≡ 0 base drafter)",
    )
    ap.add_argument(
        "--quantum", type=int, default=None,
        help="time-slice fairness: snapshot-preempt a lane after this many "
        "decode steps while requests queue (dense layout only; exact "
        "restore, no recompute)",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="print tokens as they decode (engine.stream() events) instead "
        "of per-tenant lines at retirement",
    )
    ap.add_argument(
        "--dtype", default="float32",
        help="float32 default (the tightest verification bar); bfloat16 is "
        "the served dtype and is verified at its own tolerance "
        "(logit_tolerance)",
    )
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument(
        "--no-telemetry", action="store_true",
        help="disable the engine's metrics + span tracing (the default-on "
        "telemetry costs ~µs/step; this is the A/B switch)",
    )
    ap.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="write the final metrics snapshot here (.prom/.txt → "
        "Prometheus text exposition, anything else → JSON)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write the request-span timeline here as Chrome trace_event "
        "JSON — load it in Perfetto (ui.perfetto.dev) or chrome://tracing",
    )
    args = ap.parse_args(argv)
    if args.no_telemetry and (args.metrics_out or args.trace_out):
        ap.error("--metrics-out/--trace-out need telemetry; drop --no-telemetry")

    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    cfg = cfg.replace(dtype=args.dtype)
    layout = "paged" if args.paged else args.layout
    if layout == "paged" and cfg.family == "ssm":
        ap.error(
            f"--layout paged: family {cfg.family!r} ({cfg.name}) has no "
            "attention layers to page — its per-lane state is already O(1); "
            "use --layout auto (and consider --quantum for fairness)"
        )
    if args.quantum is not None and layout == "paged":
        ap.error("--quantum needs the dense oracle layout; drop --layout paged")
    # the driver submits for every tenant it registers, so the *store* must
    # hold them all at once: without a cold tier that means one hot slot
    # each (LRU eviction is exercised in tests/test_serving); with one, the
    # hot tier may be tiny — overflow spills and admission promotes back.
    n_slots = args.slots
    if args.cold_slots == 0:
        n_slots = max(args.slots, args.tenants + 1)
        if n_slots != args.slots:
            print(f"[serve_multi] raising --slots {args.slots} → {n_slots} to hold all tenants")
    elif (n_slots - 1) + args.cold_slots < args.tenants - 1:
        ap.error(
            f"--tenants {args.tenants} exceeds hot+cold capacity "
            f"({n_slots - 1} + {args.cold_slots}); raise --cold-slots"
        )
    econf = EngineConfig(
        layout=layout,
        n_lanes=args.lanes,
        n_slots=n_slots,
        max_len=args.max_len,
        collect_logits=not args.no_verify,
        seed=args.seed,
        block_size=args.block_size,
        n_blocks=args.n_blocks,
        share_prefix=args.share_prefix,
        watermark=args.watermark,
        quantum=args.quantum,
        cold_slots=args.cold_slots,
        cold_path=args.cold_path,
        shard_lam=args.shard_lam,
        telemetry=not args.no_telemetry,
        prefill_chunk=args.prefill_chunk,
        speculate_k=args.speculate_k,
        draft_lam_rank=args.draft_lam_rank,
        base_dtype=args.base_dtype,
        shard_ba=args.shard_ba,
    )
    if args.replicas > 1 or args.disaggregate:
        if args.disaggregate and args.replicas < 2:
            ap.error("--disaggregate needs --replicas >= 2 (one to prefill, "
                     "one to decode)")
        if args.stream or args.quantum is not None:
            ap.error("--replicas serves via the router (no --stream/--quantum)")
    return args, cfg, econf


def main(argv=None):
    args, cfg, econf = configure(argv)
    use_compile_cache()
    if args.replicas > 1 or args.disaggregate:
        return _serve_replicated(args, cfg, econf)
    engine = MultiTenantEngine(cfg, econf)
    print(f"[serve_multi] family={cfg.family} layout={engine.layout}")
    reg = engine.lam_store
    if args.base_dtype != "bf16":
        from repro.core.quantize import resident_base_bytes

        qb, fb = resident_base_bytes(engine.params)
        print(
            f"[serve_multi] quantized base ({args.base_dtype}): adapted "
            f"projections resident at {qb} B vs {fb} B bf16-equivalent "
            f"({fb / max(qb, 1):.2f}x)"
        )
    if args.shard_ba:
        import jax as _jax
        print(
            f"[serve_multi] QR factors B/A rank-sharded over "
            f"{len(_jax.devices())} device(s)"
        )
    if args.shard_lam:
        import jax as _jax
        print(
            f"[serve_multi] λ-tables sharded over {len(_jax.devices())} "
            f"device(s): {reg.n_slots} slots, "
            f"{reg.table_bytes() // len(_jax.devices())} bytes/device "
            f"(replicated would be {reg.table_bytes()})"
        )
    if args.cold_slots:
        print(
            f"[serve_multi] λ-store tiers: hot={reg.hot_capacity} slots "
            f"({reg.table_bytes()} B HBM) cold={args.cold_slots} tenants "
            f"(≤{reg.bytes_per_tenant() * args.cold_slots} B host)"
        )
    if engine.paged:
        print(
            f"[serve_multi] paged KV: block_size={args.block_size} "
            f"pool={engine.allocator.capacity} blocks "
            f"share_prefix={args.share_prefix} watermark={args.watermark} "
            f"prefill_chunk={args.prefill_chunk} "
            f"cache_bytes={engine.kv_cache_bytes()}"
        )

    # tenant 0 = base model (slot 0, λ ≡ 0); the rest get distinct random λ
    lams = tenant_lambdas(engine.params, args.tenants, args.seed, args.lam_scale)
    for name, lam in lams.items():
        if name != BASE_TENANT:
            engine.add_tenant(name, lam)
    print(
        f"[serve_multi] arch={cfg.name} tenants={args.tenants} lanes={args.lanes} "
        f"slots={econf.n_slots} bytes/tenant={engine.lam_store.bytes_per_tenant()}"
    )

    reqs = {}  # uid → Request (carries .tenant and .prompt)
    prompts = tenant_prompts(lams, cfg.vocab_size, args.prompt_len, args.seed)
    for tenant, prompt in prompts.items():
        r = engine.submit(tenant, prompt, args.gen_len)
        reqs[r.uid] = r

    t0 = time.time()
    if args.stream:
        # streaming token delivery: each event prints the moment its shared
        # decode step finishes, not when its request retires
        for ev in engine.stream():
            print(f"[stream] step={engine.steps:<4d} {ev.tenant:<10s} "
                  f"lane={ev.lane} tok[{ev.index}]={ev.token}"
                  + ("  <done>" if ev.done else ""))
        done = dict(reqs)  # stream() drained the queue
    else:
        done = engine.run()
    dt = time.time() - t0
    print(
        f"[serve_multi] {engine.decoded_tokens} tokens in {dt*1e3:.1f} ms "
        f"({engine.decoded_tokens/dt:.0f} tok/s) over {engine.steps} shared "
        "decode steps"
    )
    if args.quantum is not None:
        print(f"[serve_multi] quantum={args.quantum}: "
              f"{engine.slice_preemptions} snapshot time-slices")
    if args.speculate_k:
        print(
            f"[serve_multi] speculative k={args.speculate_k}"
            + (f" draft_lam_rank={args.draft_lam_rank}"
               if args.draft_lam_rank else " (base drafter)")
            + f": {engine.drafted_tokens} drafted, "
            f"{engine.accepted_drafts} accepted "
            f"(acceptance={engine.acceptance_rate:.0%}) over "
            f"{engine.spec_steps} draft+verify steps"
        )
    if args.cold_slots:
        print(
            f"[serve_multi] λ churn: {reg.spills} spills, {reg.promotes} "
            f"promotes, {reg.cold_registers} cold registers, "
            f"{engine.deferred_promotions} deferred admissions, "
            f"cold_bytes={reg.cold_bytes()}"
        )
    if engine.paged:
        msg = (
            f"[serve_multi] pool peak={engine.allocator.peak_in_use}/"
            f"{engine.allocator.capacity} blocks, "
            f"preemptions={engine.preemptions}, cow_forks={engine.cow_forks}"
        )
        if engine.prefix_cache is not None:
            msg += (
                f", prefix hits={engine.prefix_cache.hits} "
                f"misses={engine.prefix_cache.misses} "
                f"cached={engine.prefix_cache.cached_blocks} blocks"
            )
        print(msg)
    if not args.no_telemetry:
        tel = engine.telemetry
        print(
            f"[serve_multi] latency: ttft p50≤{tel.ttft.quantile(0.5):g}ms "
            f"p95≤{tel.ttft.quantile(0.95):g}ms · tbt mean={tel.tbt.mean:.2f}ms "
            f"p95≤{tel.tbt.quantile(0.95):g}ms · e2e p95≤{tel.e2e.quantile(0.95):g}ms "
            "(bucket upper bounds)"
        )
        if args.metrics_out:
            write_metrics(args.metrics_out, engine.metrics())
            print(f"[serve_multi] metrics snapshot → {args.metrics_out}")
        if args.trace_out:
            tel.write_trace(args.trace_out)
            print(f"[serve_multi] request-span trace → {args.trace_out} "
                  "(open in ui.perfetto.dev)")
    for uid in sorted(done):
        print(f"[serve_multi] {done[uid].tenant}: {done[uid].tokens[:12]}")

    if args.no_verify:
        return done

    worst = verify_tenants(
        cfg, engine.params, lams,
        [(r.tenant, r.prompt, r.tokens, r.logits) for r in done.values()],
        args.gen_len, args.max_len, logit_tolerance(cfg.dtype, engine.base_dtype),
    )
    print(f"[serve_multi] all {len(done)} tenants match merged-weight refs "
          f"(worst |Δlogits|={worst:.2e})")
    return done


def _serve_replicated(args, cfg, econf):
    """--replicas N path: one engine per replica behind the adapter-locality
    router, same tenants/prompts/verification as the single-engine loop."""
    import dataclasses

    overrides = None
    if econf.cold_path:
        # one mmap file per replica — the cold catalog is per-store state
        overrides = lambda i, c: dataclasses.replace(
            c, cold_path=f"{c.cold_path}.r{i}")
    replicas = build_replicas(
        cfg, econf, args.replicas, config_overrides=overrides)
    router = Router(replicas, disaggregate=args.disaggregate)
    params = replicas[0].engine.params
    roles = " ".join(f"{r.name}:{r.role}" for r in router.replicas)
    print(f"[serve_multi] family={cfg.family} replicas={args.replicas} "
          f"({roles}) disaggregate={args.disaggregate}")

    lams = tenant_lambdas(params, args.tenants, args.seed, args.lam_scale)
    router.add_tenants(lams)

    routed = {}
    prompts = tenant_prompts(lams, cfg.vocab_size, args.prompt_len, args.seed)
    for tenant, prompt in prompts.items():
        r = router.submit(tenant, prompt, args.gen_len)
        routed[r.uid] = r

    t0 = time.time()
    done = router.run()
    dt = time.time() - t0
    if set(done) != set(routed):
        raise SystemExit(
            f"router lost requests: {sorted(set(routed) - set(done))}")
    n_tok = sum(len(r.tokens) for r in done.values())
    print(f"[serve_multi] {n_tok} tokens in {dt*1e3:.1f} ms "
          f"({n_tok/dt:.0f} tok/s) across {args.replicas} replicas")
    print(f"[serve_multi] placement hit rate "
          f"{router.placement_hit_rate():.0%}; transfers: "
          f"{router.transport.stats()}")
    for rep in router.replicas:
        eng = rep.engine
        line = (f"[serve_multi]   {rep.name} ({rep.role}): "
                f"{eng.decoded_tokens} tokens, {eng.steps} steps")
        if eng.paged and eng.prefix_cache is not None:
            line += (f", prefix hits={eng.prefix_cache.hits} "
                     f"misses={eng.prefix_cache.misses}")
        print(line)
    if args.metrics_out:
        write_metrics(args.metrics_out, router.metrics())
        print(f"[serve_multi] router metrics snapshot → {args.metrics_out}")
    for uid in sorted(done):
        print(f"[serve_multi] {done[uid].tenant}: {done[uid].tokens[:12]}")

    if args.no_verify:
        return done
    worst = verify_tenants(
        cfg, params, lams,
        [(r.tenant, r.prompt, r.tokens, r.engine_req.logits) for r in done.values()],
        args.gen_len, args.max_len,
        logit_tolerance(cfg.dtype, replicas[0].engine.base_dtype),
    )
    print(f"[serve_multi] all {len(done)} routed tenants match merged-weight "
          f"refs (worst |Δlogits|={worst:.2e})")
    return done


if __name__ == "__main__":
    main()
