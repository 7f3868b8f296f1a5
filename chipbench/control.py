"""Readings that a cell's limit is set from, in one process.

    python -m chipbench.control --workload <name> --seconds <s> --seeds <a,b,...> [--control <c,d,...>]

For each seed of ``--seeds`` it serves a short window at the cell's own
load, exactly as a run does, and prints the widest gap between the
reference's best logit and its logit of a served token (the program's
reading). For the seeds of ``--control`` it also prints the widest gap
of the tokens that the float8 control puts first at the same positions,
and whether that reading would pass as ``correct`` (it must not).
The limit in ``limits/<workload>.json`` lies between the largest
program reading and the smallest control reading. The benchmark's own
runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import bench, run


def readings(cell: run.Cell, seed: int, seconds: float, control: bool,
             chip=None) -> dict:
    served = run.serve(cell, seed, seconds, False, chip=chip)
    sample = run.sample_for_check(served.clients, served.t0, served.t_end,
                                  int(cell.mix["check_requests"]), seed)
    served.release()
    pad = served.traffic.longest_request
    out = {"seed": seed, "attempted": len(served.sent),
           "failed": served.failed, "checked_requests": len(sample),
           "checked_tokens": sum(len(s.tokens) for s in sample),
           "program_gap": run.check(cell, served.params, sample, pad)}
    limit = float(cell.limits["widest_logit_gap"]["limit"])
    out["limit"] = limit
    if control:
        gap = run.check(cell, served.params, sample, pad, control=True)
        out["control_gap"] = gap
        # the control in the program's place: correct only within the limit
        out["control_correct"] = gap is not None and gap <= limit
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    cell = run.load_cell(args.workload)
    wl = bench.workload(bench.benchmark(), args.workload)
    run.use_compile_cache()
    devices = run.chips_or_exit(int(wl["chips"]))
    ctl = {int(s) for s in args.control.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    seeds += sorted(ctl - set(seeds))
    import jax
    with jax.default_device(devices[0]):
        for seed in seeds:
            r = readings(cell, seed, args.seconds, seed in ctl)
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
