"""Readings that set the limits of ``correct``: many seeds of the program
and of its control, in one process on the accelerator.

    python3 -m bench.control --workload <cell> --seconds <s> \
        --seeds 1,2,3 --control-seeds 4,5,6

Each seed is one run of the cell as ``bench.run`` makes it, with a short
window; the control runs are the same with the configuration's
``control`` switched on (its fixpoint bounds cut short).  One JSON line
per run: the seed, whether it is the control, ``correct`` and every
number compared.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

from bench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    resolved = bench_run.resolve(bench_run.load_spec(), args.workload)
    devices = bench_run.devices_or_exit(resolved["cell"]["chips"])
    bench_run.enable_compile_cache()
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    runs = [(int(s), False) for s in args.seeds.split(",") if s]
    runs += [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, is_control in runs:
        r = copy.deepcopy(resolved)
        if is_control:
            r["config"]["engine"].update(r["config"]["control"]["engine"])
        t0 = time.perf_counter()
        try:
            res = bench_run.run_cell(r, seed, args.seconds, False,
                                     t_process=t0, devices=devices)
            line = {"seed": seed, "control": is_control,
                    "correct": res["correct"],
                    "compiles_in_window": res["_compiles_in_window"],
                    "attempted": res["attempted"],
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()}}
        except Exception as e:  # a control that crashes has failed
            line = {"seed": seed, "control": is_control, "correct": False,
                    "error": repr(e)}
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
