"""The router path of the harness (four one-chip replicas behind
``Router``) on four virtual CPU devices, at CPU size: sound, and the
tenants' system prompts served from the replicas' prefix caches."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_four_replicas_sound_with_prefix_hits():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "x4_tiny.py"), "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert res["prefix_hits"] > 0
