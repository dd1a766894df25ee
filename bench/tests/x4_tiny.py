"""Run a four-replica sessions mix, cut to CPU size, on four virtual
devices; print the result line.  Run as a child process so that the
device count can be set before JAX starts."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import readers  # noqa: E402
from bench import run as R  # noqa: E402
from bench.tests.tiny import run_tiny, tiny_cell  # noqa: E402


_last_counters = []


class _Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def main():
    run = R.Window.run

    def keep(self, clock):  # the engines' counters when the window closes
        run(self, clock)
        _last_counters[:] = [e.metrics() for e in self.server.engines]

    R.Window.run = keep
    cell = tiny_cell()
    # sessions: each tenant's system prompt opens every request and is
    # sent once at set-up, so routed requests hit the replicas' caches
    mix = {"replicas": 4, "arrival": {"process": "poisson", "rate_per_s": 4.0},
           "tenants": {"count": 6, "zipf_s": 1.0}, "system_prompt": {"tokens": 16},
           "prompt": {"median": 6, "sigma": 0.8, "min": 4, "max": 16},
           "output": {"median": 4, "sigma": 0.8, "min": 2, "max": 8}, "shape_seed": 12}
    cell["mix"] = mix
    cell["workload"] = {"name": "sessions-x4", "chips": mix["replicas"]}
    cell["per_layer"] = []
    res = run_tiny(_Patch(), cell, seconds=4.0, trace=int(sys.argv[1]))
    hits = sum(readers.counter(m, "serve_prefix_hits_total") for m in _last_counters)
    print(json.dumps({**res, "prefix_hits": hits}))


if __name__ == "__main__":
    main()
