"""Record ``bench/tests/data/probe_spans.xplane.pb`` on a TPU: a few engine
steps of smollm-135m at its published widths, cut to two layers so the
trace stays small, inside the benchmark's own host spans.

    python3 bench/tests/record_probe_spans.py <out.xplane.pb>

Two requests on two lanes: a 30-token prompt prefilled whole that grows
into a third KV block on its third decode step, and a 72-token prompt
prefilled in three 32-token chunks between decode steps.  Everything is
compiled by an identical untraced pass first, so the trace holds only
steady work: ``serve.*`` spans, ``jit_serve_*`` programs, and the harness's
``bench.submit`` / ``engine.step_begin`` / ``engine.step_finish``.

The capture is then cut to what the reduction reads (``prune``): the TPU
plane's op and program events and the host thread that holds the spans.
Op names keep their HLO text up to the operands (``%fusion.3 =
bf16[2,64]{1,0} fusion``); the HLO protos and per-op statistics go.  The
XSpace message class comes from the ``xplane_pb2.py`` the installed
TensorFlow ships, loaded without importing TensorFlow.
"""
import glob
import importlib.util
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.serving import (  # noqa: E402
    BASE_TENANT, EngineConfig, MultiTenantEngine, random_lambda)


def serve(eng) -> None:
    with jax.profiler.TraceAnnotation("bench.submit"):
        eng.submit("t1", np.arange(3, 33, dtype=np.int32), 4)
        eng.submit(BASE_TENANT, np.arange(40, 112, dtype=np.int32), 2)
    while eng.scheduler.has_work:
        with jax.profiler.TraceAnnotation("engine.step_begin"):
            pending = eng.step_begin()
        with jax.profiler.TraceAnnotation("engine.step_finish"):
            eng.step_finish(pending)


def xplane_pb2():
    tf = importlib.util.find_spec("tensorflow").submodule_search_locations[0]
    spec = importlib.util.spec_from_file_location(
        "xplane_pb2", f"{tf}/tsl/profiler/protobuf/xplane_pb2.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prune(raw: str, out: str) -> None:
    xs = xplane_pb2().XSpace()
    xs.ParseFromString(Path(raw).read_bytes())
    keep = [p for p in xs.planes if p.name in ("/device:TPU:0", "/host:CPU")]
    del xs.planes[:]
    xs.planes.extend(keep)
    for pl in xs.planes:
        if pl.name == "/host:CPU":
            names = {k: m.name for k, m in pl.event_metadata.items()}
            lines = [ln for ln in pl.lines
                     if any(names[e.metadata_id].startswith("serve.") for e in ln.events)]
            del pl.lines[:]
            pl.lines.extend(lines)
        used = {e.metadata_id for ln in pl.lines for e in ln.events}
        for k in [k for k in pl.event_metadata if k not in used]:
            del pl.event_metadata[k]
        for m in pl.event_metadata.values():
            del m.stats[:]
            if " = " in m.name:
                m.name = m.name.split("(", 1)[0].rstrip()
    Path(out).write_bytes(xs.SerializeToString())


def main(out: str) -> None:
    cfg = get_config("smollm-135m")
    cfg = cfg.replace(n_layers=2, dtype="bfloat16",
                      adapter=cfg.adapter.replace(layers="last2"))
    eng = MultiTenantEngine(cfg, EngineConfig(
        layout="paged", n_lanes=2, n_slots=3, max_len=128, block_size=16,
        n_blocks=24, prefill_chunk=32))
    eng.add_tenant("t1", random_lambda(jax.random.PRNGKey(1), eng.params, 0.3))
    serve(eng)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0  # spans, not calls
    with jax.profiler.trace(d, profiler_options=opts):
        serve(eng)
    prune(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d)
    print(f"{out}: {Path(out).stat().st_size} bytes, {eng.steps} steps in all")


if __name__ == "__main__":
    main(sys.argv[1])
