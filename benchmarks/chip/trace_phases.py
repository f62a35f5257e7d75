#!/usr/bin/env python3
"""A traced run of one benchmark cell, with the program's phase spans
reduced as well.

    python3 benchmarks/chip/trace_phases.py --workload <cell> --seed <n> \
        --seconds <s>

Runs ``run.py --trace 1`` in this process. Before the trace is deleted it
is read a second time by ``chipbench.phases``: the run logs the idle
seconds of the window by innermost span and the host time of each phase
per tier, its result line names each idle gap after the program's span
(``during engine.decode.sync``), and one more JSON line follows it:
``{"phases": {"step_host_ms": {"tier0": ...}, "step_idle_share": ...,
"idle_by_span": {...}, "spans": [...]}}``. Needs a TPU, as run.py does.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main() -> int:
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from chipbench import cell, phases, tracing

    found: dict = {}
    plain = cell.reduce_trace

    def reduce_trace(trace_dir: str) -> dict:
        out = plain(trace_dir)
        path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        device, host, _ = tracing.load(path)
        ph = phases.reduce(device, host, tracing.window_of(host))
        found.update(phases.summary(ph))
        for n, v in found["idle_by_span"].items():
            cell.log(f"idle {v:.4f} s under {n}")
        for row in found["spans"]:
            cell.log(f"span {row['tier']} {row['span']}: {row['count']} "
                     f"x, {row['total_s']:.4f} s, sync {row['sync_s']:.4f} s")
        out["idle_gaps"] = ph["idle_gaps"]
        return out

    cell.reduce_trace = reduce_trace
    sys.argv = [str(HERE / "run.py")] + sys.argv[1:] + ["--trace", "1"]
    rc = run.main()
    if rc == 0:
        print(json.dumps({"phases": found}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
