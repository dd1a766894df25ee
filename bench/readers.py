"""What the per-layer metrics compute; each ``layer_metrics/<metric>.py``
names one of these.  A reader gets the run's context (see
``run.per_layer``) and returns a number, or None where the traced window
holds nothing to read.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import flops
from bench import trace as tracefile


def device_idle(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device,
    averaged over the chips (%)."""
    s = ctx["summary"]
    if s["window_s"] <= 0 or not ctx["trace"].devices:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def kernel_roofline(ctx, kernel: str, count) -> Optional[float]:
    """Least time the chip could take for every call of ``kernel`` in the
    window — the larger of operations over peak FLOP/s and bytes over peak
    bandwidth, per call, from its shapes — over the calls' summed device
    time (%)."""
    evs = tracefile.kernel_events(ctx["trace"], kernel, ctx["lo"], ctx["hi"])
    if not evs:
        return None
    pk = ctx["peaks"]
    least, took, mem_bound = 0.0, 0.0, 0
    for e in evs:
        fl, nb = count(tracefile.operand_shapes(e.name))
        t_c, t_m = fl / pk["bf16_flops_per_s"], nb / pk["hbm_bytes_per_s"]
        least += max(t_c, t_m)
        mem_bound += t_m >= t_c
        took += (e.end - e.start) * 1e-9
    ctx["log"](f"{kernel}: {len(evs)} calls, {took:.6f} s on the device, "
               f"memory-bound in {mem_bound} of them")
    return 100.0 * least / took if took > 0 else None


def bgmv_roofline(ctx) -> Optional[float]:
    return kernel_roofline(ctx, "qrlora_bgmv", flops.bgmv)


def step_mfu(ctx) -> Optional[float]:
    """Model operations of every prefill and decode token delivered in the
    traced window, over the window times the chips' bf16 peak (%)."""
    toks = ctx["tokens"]
    if not toks or ctx["summary"]["window_s"] <= 0:
        return None
    arch, ad = ctx["arch"], ctx["adapter"]
    total = 0.0
    for P, j in toks:
        total += (flops.prefill_flops(arch, ad, 0, P) if j == 0
                  else flops.token_flops(arch, ad, P + j))
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * total / (ctx["summary"]["window_s"] * peak)


def _series(snap: Dict, name: str):
    fam = snap.get(name)
    return fam["series"] if fam else []


def counter(snap: Dict, name: str, **labels) -> float:
    return sum(s.get("value", 0.0) for s in _series(snap, name)
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def hist(snap: Dict, name: str):
    """(sum, count) of a histogram."""
    ss = _series(snap, name)
    return sum(s["sum"] for s in ss), sum(s["count"] for s in ss)


def engines_delta(ctx, fn) -> float:
    before, after = ctx["counters"]
    return sum(fn(a) - fn(b) for b, a in zip(before["engines"], after["engines"]))


def queue_wait_ms(ctx) -> Optional[float]:
    """Exact mean of ``serve_queue_wait_ms`` over the window (sum/count)."""
    s = engines_delta(ctx, lambda m: hist(m, "serve_queue_wait_ms")[0])
    n = engines_delta(ctx, lambda m: hist(m, "serve_queue_wait_ms")[1])
    return s / n if n else None
