"""A cell cut to a size the CPU runs in seconds, driven through the
harness's own ``run`` past its look for a chip."""
import argparse
import json
from pathlib import Path

import jax

from bench import run as R

PEAKS = json.loads((Path(R.BENCH) / "peaks.json").read_text())["TPU v5 lite"]


def tiny_cell(workload="smollm-135m.chat-zipf", rate=4.0):
    cell = R.load_cell(workload)
    conf, mix = cell["conf"], cell["mix"]
    conf["architecture"].update(
        hidden_size=48, intermediate_size=96, num_hidden_layers=3,
        num_attention_heads=3, num_key_value_heads=3, vocab_size=256)
    conf["adapter"].update(last_layers=2, rank_cap=8)
    conf["engine"].update(n_lanes=4, max_len=64, block_size=8, prefill_chunk=16)
    # the widest gap at this size: sound runs read 0 to 0.00042 and the
    # fp8 control 0.016 to 0.065 (three seeds, XLA on the CPU)
    cell["checks"]["widest_gap"]["limit"] = 0.004
    mix["arrival"]["rate_per_s"] = rate
    mix["tenants"]["count"] = 6
    mix["prompt"].update(median=12, min=4, max=40)
    mix["output"].update(median=4, min=2, max=8)
    if mix.get("system_prompt"):
        mix["system_prompt"]["tokens"] = 16
        mix["prompt"].update(median=6, min=4, max=16)
    return cell


def run_tiny(monkeypatch, cell, seed=2**31 + 9, seconds=3.0, trace=0):
    monkeypatch.setattr(R, "pool_blocks", lambda conf, dev, bb: 96)
    monkeypatch.setattr(R, "peak_bytes", lambda devs: 0)
    n = cell["workload"]["chips"]
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, sweep=None)
    return R.run(args, cell, jax.devices()[:n], PEAKS)
