"""Smoke run of the served path on TPU: does it start, and is it right?

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four one-chip replicas (four chips)

One chip (default): the engine calls ``repro.launch.serve_multi`` makes,
at smollm-135m's published width (30 layers, d_model 576, 9 heads / 3 KV
heads, d_ff 1536, vocab 49152; weights random from ``--seed``) in bfloat16
with the paged KV layout: 16 tenants (slot 0 the base model) on 8 lanes,
128-token prompts, 32 generated tokens each.  Every tenant is verified
against its merged-weight reference (``serve_multi.verify_tenants``), and
the compiled decode step must hold the BGMV Pallas kernel.

``--four-chips``: only the replica path — ``build_replicas(…, 4)`` behind
the adapter-locality ``Router``, each replica committed to its own chip,
same width and traffic — compared token for token with one engine on one
chip.

Times printed here are a smoke run's, not a benchmark.  The last line of
stdout is ``{"ok": true, "device": {...}}``; a run without a TPU, or with
any phase failing, exits non-zero without it.  Everything runs in this one
process, which owns the chip(s).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.launch import serve_multi  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serving import (  # noqa: E402
    BASE_TENANT,
    MultiTenantEngine,
    Router,
    build_replicas,
)

SERVE_ARGS = [
    "--arch", "smollm-135m", "--dtype", "bfloat16", "--layout", "paged",
    "--tenants", "16", "--lanes", "8", "--prompt-len", "128",
    "--gen-len", "32", "--max-len", "256",
]


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included: a hit is recorded as its read time), and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def load_tenants(engine, cfg, args):
    """serve_multi's single-engine traffic: register the tenants, submit one
    prompt each.  Returns (λ trees, prompts) by tenant."""
    lams = serve_multi.tenant_lambdas(
        engine.params, args.tenants, args.seed, args.lam_scale
    )
    for name, lam in lams.items():
        if name != BASE_TENANT:
            engine.add_tenant(name, lam)
    prompts = serve_multi.tenant_prompts(
        lams, cfg.vocab_size, args.prompt_len, args.seed
    )
    for tenant, prompt in prompts.items():
        engine.submit(tenant, prompt, args.gen_len)
    return lams, prompts


def check_bgmv_kernel(engine) -> None:
    """The compiled decode step must run the adapter matmuls as the BGMV
    Pallas kernel, not as the XLA ``take`` fallback."""
    t0 = time.perf_counter()
    text = engine.lower_decode().compile().as_text()
    log(f"decode step compiled in {time.perf_counter() - t0:.3f} s")
    if "tpu_custom_call" not in text or "qrlora_bgmv" not in text:
        raise SystemExit("the compiled decode step holds no BGMV kernel")
    log("compiled decode step holds the BGMV kernel (tpu_custom_call qrlora_bgmv)")


def one_chip(cfg, args, econf, clock: CompileClock) -> None:
    t0 = time.perf_counter()
    engine = MultiTenantEngine(cfg, econf)
    jax.block_until_ready(engine.params)
    log(f"engine set-up (param init + pivoted QR) {time.perf_counter() - t0:.3f} s")
    c0 = clock.seconds
    check_bgmv_kernel(engine)
    lams, _ = load_tenants(engine, cfg, args)
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    log(f"compile seconds (decode step + serving, cache reads included) "
        f"{clock.seconds - c0:.3f}, persistent-cache hits {clock.cache_hits}")
    if len(done) != args.tenants:
        raise SystemExit(f"{len(done)} of {args.tenants} requests answered")
    log(f"smoke run, not a benchmark: {engine.decoded_tokens} tokens for "
        f"{len(done)} requests in {wall:.3f} s wall, compiles included, over "
        f"{engine.steps} decode steps")

    tol = serve_multi.logit_tolerance(cfg.dtype, engine.base_dtype)
    worst = serve_multi.verify_tenants(
        cfg, engine.params, lams,
        [(r.tenant, r.prompt, r.tokens, r.logits) for r in done.values()],
        args.gen_len, args.max_len, tol,
    )
    log(f"all {len(done)} tenants verified: worst |Δlogits| {worst:.4f} "
        f"(tolerance {tol})")


def four_chips(cfg, args, econf) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devices)}")
    t0 = time.perf_counter()
    one = MultiTenantEngine(cfg, econf, device=devices[0])
    lams, prompts = load_tenants(one, cfg, args)
    want = one.run()
    log(f"one engine on {devices[0]}: {len(want)} requests in "
        f"{time.perf_counter() - t0:.3f} s, set-up and compiles included")

    t0 = time.perf_counter()
    replicas = build_replicas(cfg, econf, 4, params=one.params)
    for i, rep in enumerate(replicas):
        eng = rep.engine
        leaves = jax.tree_util.tree_leaves(
            (eng.params, eng.cache, eng.lam_store.tables)
        )
        placed = {d for leaf in leaves for d in leaf.devices()}
        if placed != {devices[i]}:
            raise SystemExit(f"replica {i} holds arrays on {placed}, not {devices[i]}")
    log("replica i's params, KV pool and λ tables sit on device i only")
    router = Router(replicas)
    router.add_tenants(lams)
    routed = {t: router.submit(t, p, args.gen_len) for t, p in prompts.items()}
    done = router.run()
    log(f"router over 4 replicas: {len(done)} requests in "
        f"{time.perf_counter() - t0:.3f} s, set-up and compiles included; "
        f"per replica " + ", ".join(
            f"{r.name}:{r.engine.decoded_tokens} tokens" for r in replicas))
    if len(done) != len(routed):
        raise SystemExit(f"router answered {len(done)} of {len(routed)} requests")
    by_tenant = {r.tenant: r for r in want.values()}
    worst = 0.0
    for tenant, r in routed.items():
        ref = by_tenant[tenant]
        if r.tokens != ref.tokens:
            raise SystemExit(f"{tenant}: routed tokens differ from one engine's")
        worst = max(worst, float(np.abs(
            np.stack(r.engine_req.logits) - np.stack(ref.logits)).max()))
    log(f"routed tokens equal one engine's for all {len(routed)} tenants "
        f"(max |Δlogits| {worst:.3g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the four one-chip replicas behind the router, "
        "against one engine on one chip (needs 4 chips)",
    )
    opts = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {backend!r}", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    clock = CompileClock()
    args, cfg, econf = serve_multi.configure(SERVE_ARGS)
    dev = jax.devices()[0]
    log(f"device_kind={dev.device_kind!r} platform={dev.platform} "
        f"count={len(jax.devices())} compile cache {cache_dir}")
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}, layout {econf.layout}")
    if opts.four_chips:
        four_chips(cfg, args, econf)
    else:
        one_chip(cfg, args, econf, clock)
    log(f"backend compile seconds this process {clock.seconds:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
