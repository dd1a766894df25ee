"""What the program's own names in a profiler trace say: its device
programs (``XLA Modules`` events, named ``jit_serve_<program>`` by the
engine) and its host spans (``serve.<name>`` on ``/host:CPU``, with stats
such as the request's ``uid``).

The readers ``decode_ms``, ``prefill_ms_per_step`` and ``pool_upkeep_ms``
take a run's context (see ``run.per_layer``) and read only the modules
``trace.load`` keeps; each returns None where the decode program never ran
in the traced window, as in a program that does not name it.

The host spans are not in ``trace.Trace``.  ``python3 bench/programs.py
--out <file.json> -- <bench/run.py arguments>`` runs one cell through
``bench/run.py`` and keeps them while the trace is read; it writes where the
device's idle time went by ``serve.*`` span, the device time of each op kind
by program, the decode step's work by lanes and attend bucket, each
request's time to first token split into queue wait and prefill, and the
gaps between the window's steps by what each step did besides decoding.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DECODE = "jit_serve_decode"
PREFILL = "jit_serve_prefill"  # and its _paged, _chunk, _chunk_final variants
KV_POOL = tuple(f"jit_serve_{p}" for p in (
    "append_block", "reset_lane", "fork_block", "import_blocks", "adopt_lane",
    "restore_lane", "extract_lane", "commit_advance"))

Span = Tuple[str, float, float, Dict]  # name, start ns, end ns, stats


# -- device programs -----------------------------------------------------------


def module_runs(tr, lo: float, hi: float) -> Dict[str, List[float]]:
    """[runs, device seconds] of each program starting in [lo, hi], summed
    over the devices."""
    from bench import trace as tracefile

    out: Dict[str, List[float]] = {}
    for mods in tr.modules.values():
        for k, (c, t) in tracefile.module_seconds(mods, lo, hi).items():
            m = out.setdefault(k, [0, 0.0])
            m[0] += c
            m[1] += t
    return out


def _per_decode_ms(ctx, programs) -> Optional[float]:
    """Device milliseconds of ``programs`` over the decode program's runs."""
    runs = module_runs(ctx["trace"], ctx["lo"], ctx["hi"])
    n = runs.get(DECODE, [0])[0]
    if not n:
        return None
    return 1e3 * sum(t for k, (_, t) in runs.items() if programs(k)) / n


def decode_ms(ctx) -> Optional[float]:
    """Mean device time of one decode step (ms)."""
    return _per_decode_ms(ctx, lambda k: k == DECODE)


def prefill_ms_per_step(ctx) -> Optional[float]:
    """Device time of every admission prefill and prefill chunk in the
    window, per decode step (ms)."""
    return _per_decode_ms(ctx, lambda k: k.startswith(PREFILL))


def pool_upkeep_ms(ctx) -> Optional[float]:
    """Device time of the KV pool's own programs (lane growth, retirement
    reset, copy-on-write, imports, snapshots), per decode step (ms)."""
    return _per_decode_ms(ctx, lambda k: k in KV_POOL)


def op_seconds_by_program(tr, lo: float, hi: float) -> Dict[str, Dict[str, float]]:
    """Self seconds of each kind of op starting in [lo, hi], by the program
    run it falls in (``none`` outside every run), summed over devices."""
    import bisect

    from bench import trace as tracefile

    out: Dict[str, Dict[str, float]] = {}
    for dev, ops in tr.devices.items():
        mods = tr.modules.get(dev, [])
        starts = [m.start for m in mods]
        for o, t in zip(ops, tracefile.self_ns(ops)):
            if not lo <= o.start < hi:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            prog = (tracefile.module_name(mods[i].name)
                    if i >= 0 and o.start < mods[i].end else "none")
            kinds = out.setdefault(prog, {})
            k = tracefile.op_kind(o.name)
            kinds[k] = kinds.get(k, 0.0) + t * 1e-9
    return out


# -- host spans ------------------------------------------------------------------


def program_spans(path: str) -> List[Span]:
    """Every ``serve.*`` host span of a trace file, by start."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.end_ns, {k: v for k, v in e.stats})
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def idle_by_span(tr, spans, lo: float, hi: float) -> Dict[str, float]:
    """Device idle seconds in [lo, hi] by the innermost of ``spans``
    ((name, start, end, ...)) open at the time, averaged over the devices;
    the part none covers is ``untraced``."""
    from bench import trace as tracefile

    named = [s[:3] for s in spans]
    out: Dict[str, float] = {}
    for ops in tr.devices.values():
        for k, v in tracefile.attribute(tracefile.idle_gaps(ops, lo, hi), named).items():
            out[k] = out.get(k, 0.0) + v * 1e-9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in out.items()}


def host_idle_ms_per_step(tr, spans: List[Span], lo: float, hi: float):
    """Device idle inside the program's spans per engine step (one
    ``serve.sync`` each), and its split by span name (ms per step); None
    where no step synced in the window."""
    steps = sum(1 for s in spans if s[0] == "serve.sync" and lo <= s[1] < hi)
    if not steps:
        return None, {}
    split = {k: 1e3 * v / steps for k, v in idle_by_span(tr, spans, lo, hi).items()
             if k != "untraced"}
    return sum(split.values()), split


# -- the report ---------------------------------------------------------------------


def report(tr, spans: List[Span], requests, steps) -> Dict:
    """What the traced window's spans and programs say (see module doc)."""
    from bench import trace as tracefile

    lo, hi = tracefile.window_of(tr)
    inside = [s for s in spans if lo <= s[1] < hi]
    harness = idle_by_span(tr, tr.spans, lo, hi)
    both = idle_by_span(tr, list(tr.spans) + inside, lo, hi)
    serve_s = sum(v for k, v in both.items() if k.startswith("serve."))
    step_s = sum(harness.get(k, 0.0) for k in ("engine.step_begin", "engine.step_finish"))
    per_step, split = host_idle_ms_per_step(tr, inside, lo, hi)
    names: Dict[str, List[float]] = {}
    for name, s, e, _ in inside:
        c = names.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-6
    # each decode dispatch with the device time of the decode run it started
    decodes = sorted((o.start, o.end) for mods in tr.modules.values() for o in mods
                     if tracefile.module_name(o.name) == DECODE and lo <= o.start < hi)
    dispatches = []
    for name, s, _, a in inside:
        if name == "serve.dispatch":
            run = next(((ds, de) for ds, de in decodes if ds >= s), None)
            dispatches.append({"lanes": a.get("lanes"), "attend_blocks": a.get("attend_blocks"),
                          "decode_ms": (run[1] - run[0]) * 1e-6 if run else None})
    ctx = {"trace": tr, "lo": lo, "hi": hi}
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s_by_harness_span": harness,
        "idle_s_by_innermost_span": both,
        "serve_share_of_step_idle": serve_s / step_s if step_s else None,
        "host_idle_ms_per_step": per_step,
        "host_idle_ms_per_step_by_span": split,
        "spans_ms": {k: {"count": c, "ms": t} for k, (c, t) in sorted(names.items())},
        "programs": {k: {"runs": c, "s": t} for k, (c, t) in
                     sorted(module_runs(tr, lo, hi).items(), key=lambda kv: -kv[1][1])},
        "model_step.decode_ms": decode_ms(ctx),
        "model_step.prefill_ms_per_step": prefill_ms_per_step(ctx),
        "kv_pool.upkeep_ms_per_step": pool_upkeep_ms(ctx),
        "op_seconds_by_program": {k: dict(tracefile.top(v, 5)) for k, v in
                                  op_seconds_by_program(tr, lo, hi).items()},
        "decode_steps": dispatches,
        "prefills": [{"span": n[6:], "ms": (e - s) * 1e-6, **a} for n, s, e, a in inside
                     if n in ("serve.prefill", "serve.prefill_chunk") and "uid" in a],
        "requests": requests,
        "step_gaps": step_gaps(steps),
    }


def step_gaps(steps) -> Dict:
    """Host time between the ends of consecutive decode steps of the window
    (ms), by what the later step did besides decoding: prompt work
    (an admission prefill or a chunk), a KV block appended, or neither.
    ``tbt_like_p50_ms`` weights each gap by its decoding lanes, as the
    median gap between tokens does."""
    import numpy as np

    by: Dict[str, List[float]] = {"prompt": [], "append": [], "plain": []}
    gaps, lanes = [], []
    for (t0, *_), (t1, n, appended, prompt) in zip(steps, steps[1:]):
        g = (t1 - t0) * 1e3
        by["prompt" if prompt else "append" if appended else "plain"].append(g)
        gaps.append(g)
        lanes.append(n)
    if not gaps:
        return {}
    order = np.argsort(gaps)
    cum = np.cumsum(np.asarray(lanes, float)[order])
    return {
        "steps": len(gaps),
        "tbt_like_p50_ms": float(np.asarray(gaps)[order][np.searchsorted(cum, cum[-1] / 2)]),
        **{k: {"steps": len(v), "median_ms": float(np.median(v)) if v else None}
           for k, v in by.items()},
    }


def request_times(reqs) -> List[Dict]:
    """Each engine request's time to first token split at admission, from
    its telemetry trace (ms), slowest first."""
    out = []
    for r in reqs:
        t = r.trace
        if t is None or t.first_token_ts is None:
            continue
        admit = next((ts for ev, ts, _ in t.events if ev == "admit"), None)
        chunks = sum(1 for ev, _, _ in t.events if ev == "prefill_chunk")
        out.append({"uid": r.uid, "prompt": int(r.prompt.size), "chunks": chunks,
                    "ttft_ms": (t.first_token_ts - t.submit_ts) * 1e3,
                    "queue_ms": (admit - t.submit_ts) * 1e3,
                    "admit_to_first_ms": (t.first_token_ts - admit) * 1e3})
    return sorted(out, key=lambda d: -d["ttft_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True, help="JSON report")
    ap.add_argument("run_args", nargs=argparse.REMAINDER,
                    help="-- then the arguments of bench/run.py (with --trace 1)")
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import run as R
    from repro.serving import MultiTenantEngine

    kept: Dict = {}
    E = MultiTenantEngine
    load, submit, window = R.tracefile.load, E.submit, R.Window.run
    grow, finish = E._grow_lanes, E.step_finish
    reqs = []  # the window's, not set-up's
    steps = []  # the window's decode steps: (end, lanes, blocks appended, prompt work)

    def load_and_keep(path):
        kept["trace"] = load(path)
        kept["spans"] = program_spans(path)
        return kept["trace"]

    def submit_and_keep(self, *a):
        req = submit(self, *a)
        reqs.append(req)
        return req

    def run_window(self, clock):
        reqs.clear()
        steps.clear()
        return window(self, clock)

    def grow_and_count(self, *a):
        n = sum(map(len, self._lane_blocks.values()))
        grow(self, *a)
        self.appended = sum(map(len, self._lane_blocks.values())) - n

    def finish_and_log(self, pending):
        out = finish(self, pending)
        if pending["decoding"] is not None:
            tel = self.telemetry
            prompt = tel.prefill_ms.count + tel.prefill_chunk_ms.count
            steps.append((time.perf_counter(), len(pending["decoding"]),
                          self.appended, prompt - getattr(self, "prompt_work", 0)))
            self.prompt_work = prompt
        return out

    R.tracefile.load = load_and_keep
    R.Window.run = run_window
    E.submit, E._grow_lanes, E.step_finish = submit_and_keep, grow_and_count, finish_and_log
    rc = R.main([a for a in args.run_args if a != "--"])
    if "trace" not in kept:
        print("[programs] no trace was read: pass --trace 1", file=sys.stderr)
        return rc or 1
    out = report(kept["trace"], kept["spans"], request_times(reqs), steps)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(f"[programs] {args.out}: host idle {out['host_idle_ms_per_step']} ms/step, "
          f"serve spans hold {out['serve_share_of_step_idle']} of the engine steps' "
          f"idle", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
