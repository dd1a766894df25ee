"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), traffic mix (``bench/traffic/<mix>.json``),
correctness limits (``bench/checks/<workload>.json``) and per-layer readers
(``bench/layer_metrics/<metric>.py``) are found by name.  Nothing here
branches on a cell.

Set-up (timed as ``setup_s``) makes the weights and tenants from the seed,
builds the served entry point — one ``MultiTenantEngine``, or ``Router``
over ``build_replicas`` when the mix asks for replicas — registers every
tenant in one batch, compiles every shape the window's requests will use,
and runs the mix's prefix warm-up.  The window then offers the mix's
requests open loop for ``--seconds``.  Afterwards the program's state is
freed and a sample of finished requests is checked against the plain
reference (``reference.py``).  ``--trace 1`` also profiles a few seconds in
the middle of the window and reports the cell's per-layer metrics.

The last line of stdout is one JSON object; a run without a TPU, with
fewer chips than the cell asks for, or on a device missing from
``bench/peaks.json`` prints none and exits non-zero.

``--sweep r1,r2,...`` (for finding a cell's knee, not used by the check)
runs one window per offered rate after one set-up and prints a line each.
"""
from __future__ import annotations

import argparse
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from bench import reference, traffic, weights  # noqa: E402
from bench import trace as tracefile  # noqa: E402


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included: a hit is recorded as its read time), and cache hits.  One per
    process: listeners cannot be removed."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Unrunnable(Exception):
    """The machine cannot run the cell: no result line, non-zero exit."""


# -- the cell, by name ------------------------------------------------------


def load_cell(name: str) -> Dict:
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Unrunnable(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(ROOT / conf_entry["file"]) as f:
        conf = json.load(f)
    mix = traffic.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    with open(BENCH / "checks" / f"{name}.json") as f:
        checks = json.load(f)

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "name": name, "workload": w, "conf": conf, "mix": mix, "checks": checks,
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def check_devices(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Unrunnable(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Unrunnable(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Unrunnable(f"device kind {kind!r} has no entry in bench/peaks.json")
    return devs[:chips], peaks[kind]


def use_compile_cache() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    every program in it, so a cell's second run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the system under test ----------------------------------------------------

_ARCH_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
}


def program_config(conf: Dict):
    """The program's ModelConfig for the registry entry, with every field
    the configuration file states set as it states it."""
    from repro.configs import get_config

    cfg = get_config(conf["registry"])
    arch, ad = conf["architecture"], conf["adapter"]
    cfg = cfg.replace(
        dtype=conf["dtype"],
        d_head=arch["hidden_size"] // arch["num_attention_heads"],
        **{field: arch[key] for key, field in _ARCH_FIELDS.items()},
        adapter=cfg.adapter.replace(
            mode="qr_lora", targets=tuple(ad["targets"]),
            layers=f"last{ad['last_layers']}", rank_cap=ad["rank_cap"],
        ),
    )
    return cfg


def pool_blocks(conf: Dict, device, block_bytes: int) -> int:
    """KV blocks for the cell: all the memory the weights leave on the
    device, as a deployment sizes its pool — ``hbm_share`` of it, less a
    reserve for activations, over ``live_copies`` pools (a step that does
    not donate its cache holds the old pool and the new at once)."""
    rule = conf["kv_pool"]
    st = device.memory_stats()
    free = st["bytes_limit"] * rule["hbm_share"] - st["bytes_in_use"] - rule["reserve_bytes"]
    return int(free // (rule["live_copies"] * block_bytes))


def kv_block_bytes(arch: Dict, block_size: int) -> int:
    dh = arch["hidden_size"] // arch["num_attention_heads"]
    return arch["num_hidden_layers"] * 2 * arch["num_key_value_heads"] * dh * block_size * 2


class Server:
    """The served entry point: one engine, or a router over replicas."""

    def __init__(self, cfg, econf, params, devices, n_replicas: int, lams):
        from repro.serving import MultiTenantEngine, Router, build_replicas

        if n_replicas == 1:
            self.engines = [MultiTenantEngine(cfg, econf, params=params, device=devices[0])]
            self.router = None
        else:
            if len(devices) != n_replicas:
                raise Unrunnable(f"{n_replicas} replicas need {n_replicas} chips")
            reps = build_replicas(cfg, econf, n_replicas, params=params)
            self.engines = [r.engine for r in reps]
            self.router = Router(reps)
        self.lams = lams

    def register(self) -> None:
        for eng in self.engines:
            eng.add_tenants(self.lams)
        if self.router is not None:
            self.router.add_tenants(self.lams)

    def submit(self, tenant: str, prompt, n: int):
        """Returns (engine index, engine uid)."""
        if self.router is None:
            return 0, self.engines[0].submit(tenant, prompt, n).uid
        rr = self.router.submit(tenant, prompt, n)
        return rr.replica.replica_id, rr.engine_req.uid

    def has_work(self) -> bool:
        return any(e.scheduler.has_work for e in self.engines)

    def step(self) -> None:
        # an engine with no work is not stepped and keeps its last events
        self._stepped = [e.scheduler.has_work for e in self.engines]
        if self.router is None:
            eng = self.engines[0]
            with span("engine.step_begin"):
                pending = eng.step_begin()
            with span("engine.step_finish"):
                eng.step_finish(pending)
        else:
            with span("router.step"):
                self.router.step()

    def events(self):
        """(engine index, uid, token id) of every token the last step
        delivered, in order."""
        for i, eng in enumerate(self.engines):
            if self._stepped[i]:
                for ev in eng.events:
                    yield i, ev.uid, ev.token

    def counters(self) -> Dict:
        snap = {"engines": [e.metrics() for e in self.engines]}
        if self.router is not None:
            snap["router"] = self.router.registry.snapshot()
        return snap

    def drain(self, limit_s: float) -> None:
        t_end = time.perf_counter() + limit_s
        while self.has_work() and time.perf_counter() < t_end:
            self.step()


@contextmanager
def span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


# -- set-up -------------------------------------------------------------------


def bucket(n: int, floor: int, cap: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


def warm_set(reqs: List[traffic.Request], econf) -> List[traffic.Request]:
    """One request per class of compiled shapes the window's requests can
    take: prompt bucket, whether its prefill is split into chunks, and the
    bucket of blocks its decode attends over."""
    bs, cap = econf.block_size, econf.max_len
    C = econf.prefill_chunk or cap + 1
    seen, out = set(), []
    for r in sorted(reqs, key=lambda r: (r.prompt.size, r.max_new_tokens)):
        P = r.prompt.size
        key = (bucket(P, bs, cap), P > 2 * C,
               bucket(-(-(P + r.max_new_tokens) // bs), 1, cap // bs))
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out


def warm(server: Server, reqs: List[traffic.Request], block_size: int) -> None:
    """Drive each warm request alone through each engine until its decode
    has run twice (the longest until it has also grown into a new block),
    then withdraw it."""
    grow = max(reqs, key=lambda r: r.prompt.size % block_size + r.max_new_tokens,
               default=None)
    for eng in server.engines:
        for r in reqs:
            want = 3
            if r is grow:
                want = block_size - r.prompt.size % block_size + 2
            want = min(r.max_new_tokens, want)
            req = eng.submit(weights.tenant_name(r.tenant), r.prompt, r.max_new_tokens)
            while len(req.tokens) < want:
                eng.step()
            if not req.done:
                eng.cancel(req)
        eng.release_prefix_cache()


def prefix_warmup(server: Server, reqs: List[traffic.Request]) -> None:
    """Send the mix's set-up requests (each tenant's system prompt) and
    run them to completion."""
    if not reqs:
        return
    for r in reqs:
        server.submit(weights.tenant_name(r.tenant), r.prompt, r.max_new_tokens)
    server.drain(600.0)


# -- the window ----------------------------------------------------------------


class Window:
    """Open-loop offer of ``reqs`` for ``seconds``; host timestamps of every
    delivered token."""

    def __init__(self, server: Server, reqs: List[traffic.Request], seconds: float,
                 trace_at: Optional[float] = None, trace_s: float = 0.0,
                 trace_dir: Optional[str] = None):
        self.server, self.reqs, self.T = server, reqs, seconds
        self.trace_at, self.trace_s, self.trace_dir = trace_at, trace_s, trace_dir
        self.due = np.array([r.due_s for r in reqs])
        self.token_t: List[List[float]] = [[] for _ in reqs]
        self.served: List[List[int]] = [[] for _ in reqs]
        self.finished_at: Dict[int, float] = {}
        self.refused = 0
        self.late = []  # seconds each submit ran after its due time
        self.trace_window = None
        self.counters = None

    def run(self, clock: CompileClock) -> None:
        srv = self.server
        by_uid: Dict[tuple, int] = {}
        n, i = len(self.reqs), 0
        c0 = clock.count
        tracing = False
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= self.T:
                break
            if self.trace_at is not None and not tracing and now >= self.trace_at \
                    and self.trace_window is None:
                self.counters = [srv.counters()]
                jax.profiler.start_trace(self.trace_dir)
                tracing = True
                self.trace_window = [time.perf_counter() - t0, None]
            elif tracing and now >= self.trace_window[0] + self.trace_s:
                jax.profiler.stop_trace()
                tracing = False
                self.trace_window[1] = time.perf_counter() - t0
                self.counters.append(srv.counters())
                continue
            if i < n and self.due[i] <= now:
                with span("bench.submit"):
                    while i < n and self.due[i] <= now:
                        r = self.reqs[i]
                        try:
                            key = srv.submit(weights.tenant_name(r.tenant), r.prompt,
                                             r.max_new_tokens)
                            by_uid[key] = i
                        except ValueError:
                            self.refused += 1
                        self.late.append(now - self.due[i])
                        i += 1
            if srv.has_work():
                srv.step()
                t = time.perf_counter() - t0
                for e, uid, tok in srv.events():
                    k = by_uid.get((e, uid))
                    if k is None:
                        continue
                    self.token_t[k].append(t)
                    self.served[k].append(tok)
                    if len(self.token_t[k]) >= self.reqs[k].max_new_tokens:
                        self.finished_at[k] = t
            else:
                nxt = self.due[i] if i < n else self.T
                with span("bench.wait"):
                    time.sleep(max(0.0, min(nxt, self.T) - now))
        if tracing:
            jax.profiler.stop_trace()
            self.trace_window[1] = time.perf_counter() - t0
            self.counters.append(srv.counters())
        self.submitted = i
        self.window_compiles = clock.count - c0

    # end-to-end numbers, from the host clock

    def ttft_ms(self) -> List[float]:
        return [((t[0] if t else self.T) - d) * 1e3 for d, t in zip(self.due, self.token_t)]

    def tbt_ms(self) -> List[float]:
        return [(b - a) * 1e3 for t in self.token_t for a, b in zip(t, t[1:])]

    def out_tokens(self) -> int:
        return sum(len(t) for t in self.token_t)


def pct(xs: List[float], q: float) -> float:
    """The q-th percentile (0 < q < 100), linear between order statistics."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else float("nan")


# -- per-layer readers -----------------------------------------------------------


def reader(metric: str):
    path = BENCH / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def token_work(win: Window, lo: float, hi: float):
    """(prompt length, token index) of every token delivered in [lo, hi]
    host seconds: index 0 came from a prefill, the rest from decode."""
    out = []
    for k, ts in enumerate(win.token_t):
        for j, t in enumerate(ts):
            if lo <= t <= hi:
                out.append((win.reqs[k].prompt.size, j))
    return out


# -- one run -----------------------------------------------------------------------


def run(args, cell: Dict, devices, peaks: Dict) -> Dict:
    from repro.models.model_zoo import build_model
    from repro.serving import EngineConfig

    conf, mix = cell["conf"], cell["mix"]
    arch, eng_conf = conf["architecture"], conf["engine"]
    clock = CompileClock()
    phases: Dict[str, float] = {}
    t_setup = time.perf_counter()

    @contextmanager
    def phase(name):
        t, c = time.perf_counter(), clock.seconds
        yield
        phases[name] = time.perf_counter() - t
        phases[name + "_compile"] = clock.seconds - c

    cfg = program_config(conf)
    shapes = build_model(cfg).dryrun_params()
    with jax.default_device(devices[0]), phase("weights"):
        params = jax.block_until_ready(weights.make_params(shapes, conf, args.seed))
    lam_shapes = {
        mod: {proj: leaf["lam"].shape for proj, leaf in projs.items()}
        for mod, projs in shapes["groups"]["adapters"].items()
    }
    n_tenants = mix["tenants"]["count"]
    with phase("tenants"):
        lams = weights.tenant_lambdas(lam_shapes, conf, n_tenants, args.seed)
    reqs = traffic.generate(mix, arch["vocab_size"], args.seed, args.seconds)
    block_bytes = kv_block_bytes(arch, eng_conf["block_size"])
    n_blocks = pool_blocks(conf, devices[0], block_bytes)
    econf = EngineConfig(
        layout="paged", n_lanes=eng_conf["n_lanes"], n_slots=n_tenants + 1,
        max_len=eng_conf["max_len"], block_size=eng_conf["block_size"],
        n_blocks=n_blocks, share_prefix=eng_conf["share_prefix"],
        watermark=eng_conf["watermark"], prefill_chunk=eng_conf["prefill_chunk"],
        seed=args.seed,
    )
    with phase("engines"):
        server = Server(cfg, econf, params, devices, mix.get("replicas", 1), lams)
    with phase("register"):
        server.register()
    with phase("warmup"):
        warm(server, warm_set(reqs, econf), econf.block_size)
    with phase("prefix_warmup"):
        prefix_warmup(server, traffic.prefix_warmup(mix, arch["vocab_size"], args.seed))
    setup_s = time.perf_counter() - t_setup
    log("setup " + ", ".join(f"{k} {v:.3f} s" for k, v in phases.items())
        + f"; compile cache hits {clock.cache_hits}; KV pool {n_blocks} blocks "
        f"of {block_bytes} B on each of {len(server.engines)} engine(s)")

    if args.sweep:
        return sweep(server, mix, arch, args, clock)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    trace_s = min(3.0, args.seconds / 4)
    win = Window(server, reqs, args.seconds,
                 trace_at=args.seconds / 2 if args.trace else None,
                 trace_s=trace_s, trace_dir=trace_dir)
    win.run(clock)
    peak = peak_bytes(devices)

    ttft, tbt = win.ttft_ms(), win.tbt_ms()
    e2e = {
        "setup_s": (setup_s, "s"),
        "ttft_p95_ms": (pct(ttft, 95), "ms"),
        "tbt_p50_ms": (pct(tbt, 50), "ms"),
        "tbt_p95_ms": (pct(tbt, 95), "ms"),
        "out_tokens_per_s": (win.out_tokens() / args.seconds, "tokens/s"),
    }
    log(f"window {args.seconds} s: {len(reqs)} requests due, {win.submitted} submitted, "
        f"{len(win.finished_at)} finished, {win.out_tokens()} tokens; offered "
        f"{len(reqs) / args.seconds:.3f} req/s; ttft p50 {pct(ttft, 50):.3f} ms over "
        f"{len(ttft)} requests; {len(tbt)} gaps; generator late p50 "
        f"{pct(win.late, 50) * 1e3:.3f} ms, max {max(win.late, default=0) * 1e3:.3f} ms; "
        f"compiles in window {win.window_compiles}; peak_bytes_in_use {peak}")
    samples = sample_finished(win, args.seed, cell["checks"]["sample_tokens"])

    result: Dict = {"correct": None, "attempted": len(reqs), "failed": win.refused}
    if args.trace:
        result["metrics"], result["breakdown"], busy = per_layer(
            cell, win, trace_dir, peaks, arch, conf, len(devices))
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        busy = None

    # free the program's state before the reference runs
    del server, win.server
    gc.collect()
    lam_of = lambda t: lams[t]["attn"]  # noqa: E731
    limit = cell["checks"]["widest_gap"]["limit"]
    check = reference.widest_gap(params, arch, samples, lam_of)
    result["correct"] = judge(check, limit, win.refused)
    if getattr(args, "control", None):
        # the control in the program's place, judged by the same comparison
        ctl = reference.widest_gap(params, arch, samples, lam_of, quant=args.control)
        result["control"] = {"widest_gap": ctl["widest_gap"], "quant": args.control,
                             "correct": judge(ctl, limit, win.refused)}
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": int(peak)}
    if busy is not None:
        result["device"].update(busy)
    result["checks"] = {
        "widest_gap": {"value": check["widest_gap"], "limit": limit},
        "tokens_compared": {"value": check["tokens"], "limit": 1},
        "requests_refused": {"value": win.refused, "limit": 0},
    }
    for k, v in result["checks"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def judge(check: Dict, limit: float, refused: int) -> bool:
    """``correct``: some tokens compared, none wider than the limit below
    the reference's best, and no request refused."""
    return bool(check["widest_gap"] <= limit and check["tokens"] > 0 and refused == 0)


def peak_bytes(devices) -> int:
    """Peak memory in use on the fullest chip."""
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def sample_finished(win: Window, seed: int, want_tokens: int):
    """Finished requests drawn from the seed, the longest first, until they
    hold ``want_tokens`` served tokens."""
    done = sorted(win.finished_at)
    if not done:
        return []
    rng = np.random.default_rng([seed, 4])
    longest = max(done, key=lambda k: win.reqs[k].prompt.size + win.reqs[k].max_new_tokens)
    order = [longest] + [k for k in rng.permutation(done) if k != longest]
    out, n = [], 0
    for k in order:
        if n >= want_tokens:
            break
        r = win.reqs[k]
        out.append((weights.tenant_name(r.tenant), r.prompt, win.served[k]))
        n += len(win.served[k])
    return out


def per_layer(cell, win: Window, trace_dir: str, peaks, arch, conf, chips):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    tr = tracefile.load(paths[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = tracefile.window_of(tr)
    summary = tracefile.summarize(tr, lo, hi)
    log("device programs in the traced window [name, runs, seconds]: "
        + json.dumps(summary["modules"][:12]))
    wlo, whi = win.trace_window
    ctx = {
        "trace": tr, "lo": lo, "hi": hi, "summary": summary,
        "counters": win.counters, "tokens": token_work(win, wlo, whi),
        "arch": arch, "adapter": conf["adapter"], "peaks": peaks, "chips": chips,
        "n_lanes": conf["engine"]["n_lanes"], "log": log,
    }
    metrics = {}
    for m in cell["per_layer"]:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    busy = {"busy_s": summary["busy_s"], "window_s": summary["window_s"]}
    return metrics, breakdown, busy


def sweep(server: Server, mix, arch, args, clock: CompileClock) -> Dict:
    """One window per rate; every request still queued or running at its
    close is withdrawn before the next."""
    out = []
    for k, rate in enumerate(float(r) for r in args.sweep.split(",")):
        reqs = traffic.generate(mix, arch["vocab_size"], args.seed + k, args.seconds, rate)
        win = Window(server, reqs, args.seconds)
        win.run(clock)
        backlog = sum(len(e.scheduler.queue) for e in server.engines)
        busy = sum(len(e.scheduler.active()) for e in server.engines)
        ttft, tbt = win.ttft_ms(), win.tbt_ms()
        late = [x for x, d in zip(ttft, win.due) if d >= args.seconds / 2]
        line = {"rate": rate, "requests": len(reqs), "finished": len(win.finished_at),
                "backlog": backlog, "lanes_busy": busy,
                "out_tokens_per_s": win.out_tokens() / args.seconds,
                "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
                "ttft_p95_ms_second_half": pct(late, 95),
                "tbt_p50_ms": pct(tbt, 50), "tbt_p95_ms": pct(tbt, 95)}
        print(json.dumps({"sweep": line}), flush=True)
        out.append(line)
        for e in server.engines:
            for r in list(e.scheduler.queue) + list(e.scheduler.active()):
                e.cancel(r)
    return {"sweep": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates, one window each")
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices, peaks = check_devices(cell["workload"]["chips"])
    except Unrunnable as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    result = run(args, cell, devices, peaks)
    if args.sweep:
        return 0
    checks = result.pop("checks")
    result["checks"] = checks  # last key of the line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
