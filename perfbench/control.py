"""Readings that set a cell's correctness limits.

For each seed, one process serves a window at the cell's own load, warmed
up as a run is (so it compares as many tokens as a run does), then reads
over the served tokens, teacher forced, and over the tokens whose routing
margin is at least the cell's ``min_router_margin``:

* the program's numbers against the float32 reference (the lower reading:
  sound runs), and
* those of the token the float8 control puts first at the same positions
  (the upper reading: the reference computed one precision below the
  configuration's).

Each record also keeps every token's gaps, best logit and routing margin.
Benchmark runs never run this.

    python3 perfbench/control.py --workload <cell> --seconds 50 \
        --seeds 11 12 13 --out readings.jsonl
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import run  # noqa: E402
from perfbench.gaps import summary  # noqa: E402


def readings(cell, read) -> dict:
    """The program's and the control's numbers over what a run's check read
    (``run.run_cell``'s third result), from the reference module that the
    cell's configuration names."""
    ck = cell.checks
    per = {"gap": [], "fp8_gap": [], "best": [], "margin": []}
    reference = read["reference"]
    for rid in read["rids"]:
        r = reference.readings(read["tparams"], cell.config["model"],
                               read["prompts"][rid][0].tolist(),
                               read["win"].tokens[rid], controls=("fp8",))
        for k in per:
            per[k].append(r[k])
    out = {name: summary(per[k], per["margin"],
                         float(ck["min_router_margin"]),
                         float(ck["wide_gap"]))
           for name, k in (("program", "gap"), ("control", "fp8_gap"))}
    out["tokens"] = {k: np.concatenate(v).tolist() for k, v in per.items()}
    return out


def read_seed(workload: str, seed: int, seconds: float, log=print) -> dict:
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds)])
    out, _, read = run.run_cell(args, log=log)
    rec = readings(run.spec.load_cell(workload), read)
    del read
    gc.collect()
    return dict(rec, seed=seed, correct=out["correct"],
                attempted=out["attempted"], failed=out["failed"],
                memory_peak_bytes=out["device"]["memory_peak_bytes"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    log = lambda s: print(s, file=sys.stderr)  # noqa: E731
    with open(a.out, "a") as f:
        for seed in a.seeds:
            rec = read_seed(a.workload, seed, a.seconds, log)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            log(json.dumps({k: v for k, v in rec.items() if k != "tokens"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
