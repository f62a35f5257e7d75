#!/usr/bin/env python3
"""Readings that set a cell's check limits: the compared numbers of the
program over many seeds, and of the control (the references computed in
fp8, in the program's place) on the same served tokens, in one process.

    python3 benchmarks/chip/check_readings.py --workload mamba2-mlarge.chat \
        --seeds 11,12,13 --control-seeds 11,12 --seconds 15 \
        [--parts tier1] [--faults freeze_kv:14,alter_tokens:15]

Per seed: new weights from the seed for every tier, the router gated, the cell's
traffic at the cell's rate for a warm-up and ``--seconds``, then no more
arrivals while the pool finishes what it holds (at most ``--drain-cap``
seconds), then the checks on what it finished, as a run makes them. Prints one JSON line
per seed and a summary (the largest program reading and the smallest
control reading of every number). ``--parts`` runs only the named
references (tierN, router); ``--faults`` serves one more phase per
``name:seed`` with that fault of ``chipbench/faults.py`` planted. Needs a
TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--warm", type=float, default=5.0)
    ap.add_argument("--drain-cap", type=float, default=30.0)
    ap.add_argument("--parts", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("check_readings.py: no TPU found", file=sys.stderr)
        return 2
    from run import enable_cache
    from chipbench import cell as cellrun, harness, session, spec, traffic
    from chipbench.faults import FAULTS
    enable_cache()
    cellrun.compile_counter()
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    parts = set(args.parts.split(",")) if args.parts else None
    faults = [(f.split(":")[0], int(f.split(":")[1]))
              for f in args.faults.split(",") if f]
    for name, _ in faults:
        if name not in FAULTS:
            raise SystemExit(f"unknown fault {name!r}; known: "
                             f"{sorted(FAULTS)}")
    rate = cellrun.rate_of(cell.config, cell.mix)
    run = harness.Run(cell.config, seeds[0])
    lens = session.prompt_lengths(cell.mix, [rate], args.seconds, args.warm)
    run.warm(lens, {32 << i for i in range(8)})
    rows, fault_rows = [], []
    phases = [(None, s) for s in seeds] + faults
    for k, (fault, seed) in enumerate(phases):
        t = time.monotonic()
        if k:
            run.reseed(seed)
        run.calibrate(traffic.calibration_prompts(
            cell.mix, int(cell.config["router"]["calibration_prompts"])),
            float(cell.config["router"]["tier0_share"]))
        undo = FAULTS[fault](run) if fault else None
        ph = session.serve_phase(run, cell.mix, rate, args.seconds,
                                 args.warm, args.drain_cap)
        if undo is not None:
            undo()
        loop = ph["loop"]
        row = {"seed": seed, "drained": ph["drained"],
               "program": harness.audit(run, loop)}
        run.free_state()
        row["program"].update(harness.check(run, loop, seed, {},
                                            parts=parts))
        if fault:
            row["fault"] = fault
        elif seed in ctrl_seeds:
            row["control"] = harness.check(run, loop, seed, {},
                                           control="fp8", parts=parts)
        run.reset_pools()
        row["seconds"] = time.monotonic() - t
        (fault_rows if fault else rows).append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["program"]:
        prog = [r["program"][name]["value"] for r in rows]
        ctrl = [r["control"][name]["value"] for r in rows if "control" in r
                and name in r["control"]]
        summary[name] = {"program_max": max(prog), "program": prog,
                         "control_min": min(ctrl) if ctrl else None,
                         "control": ctrl,
                         "faults": {r["fault"]: r["program"][name]["value"]
                                    for r in fault_rows}}
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    session.write_out(f"readings-{args.workload}.json",
                      {"workload": args.workload, "rows": rows,
                       "faults": fault_rows, "summary": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
