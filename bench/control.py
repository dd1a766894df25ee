"""The comparison's two readings for a cell, on the chip: the program's
widest gap and the control's, over many seeds in one process.

    python3 bench/control.py --workload <name> --seconds 10 --seeds 101,102,...

Each seed is a whole run at the cell's own size and load (a short window
is enough to finish the mix's longest requests and to compare as many
tokens as a run does); after it, the same sampled requests are read by
the control: the plain reference computed with float8_e4m3 operands, the
precision below the configuration's bfloat16, whose top token's gap under
the float32 reference is its reading.  One JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--quant", default="fp8")
    args = ap.parse_args(argv)
    try:
        cell = R.load_cell(args.workload)
        devices, peaks = R.check_devices(cell["workload"]["chips"])
    except R.Unrunnable as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    R.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        a = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0, sweep=None, control=args.quant)
        res = R.run(a, cell, devices, peaks)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": res["checks"]["widest_gap"]["value"],
                          "tokens": res["checks"]["tokens_compared"]["value"],
                          "control": res["control"]["widest_gap"],
                          "control_correct": res["control"]["correct"],
                          "setup_s": res["metrics"]["setup_s"]["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
