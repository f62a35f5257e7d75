#!/usr/bin/env python3
"""Knee sweep: one configuration under a traffic mix's lengths at a list
of fixed arrival rates, in one process (one compile).

    python3 benchmarks/chip/sweep.py --config mamba2-mlarge --mix chat \
        --rates 0.5,1,2,3,4 --seconds 30 --seed 1

For each rate: an open loop with a warm-up, a window of ``--seconds`` and
then no more arrivals until everything submitted has finished. It prints
per rate the due-to-first-token and mean inter-token times of the
window's requests, the backlog (requests queued for a slot) at the
window's start and end, and how late the generator ran. The lowest rate
is the unloaded one: the mix's ``slo`` multiples of its p90 TTFT and p90
mean inter-token gap are the limits, and a rate meets them when at least
the mix's attainment share of its window requests is within both with no
backlog growing through the window. The knee is the highest such rate;
record it as ``knee_rps`` in the configuration's file. Needs a TPU.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warm", type=float, default=10.0)
    ap.add_argument("--drain-cap", type=float, default=120.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("sweep.py: no TPU found", file=sys.stderr)
        return 2
    import numpy as np
    from run import enable_cache
    from chipbench import cell as cellrun, harness, session, spec, stats
    enable_cache()
    tally = cellrun.compile_counter()
    config, mix = spec.load_config(args.config), spec.load_mix(args.mix)
    rates = sorted(float(r) for r in args.rates.split(","))
    run = harness.Run(config, args.seed)
    lens = session.prompt_lengths(mix, rates, args.seconds, args.warm)
    t = time.monotonic()
    n = run.warm(lens, {32 << i for i in range(8)})
    print(f"warm: {n} shapes in {time.monotonic() - t:.1f} s, "
          f"{tally['compiles']} compiles", flush=True)
    from chipbench import traffic
    run.calibrate(traffic.calibration_prompts(
        mix, int(config["router"]["calibration_prompts"])),
        float(config["router"]["tier0_share"]))
    rows = []
    for rate in rates:
        c0 = tally["compiles"]
        ph = session.serve_phase(run, mix, rate, args.seconds, args.warm,
                                 args.drain_cap)
        w0, w1 = ph["window"]
        ttft, tpot = [], []
        for s in ph["loop"].items:
            if not (w0 <= s.due < w1):
                continue
            tt = s.req.token_t if s.req is not None else []
            ttft.append(tt[0] - s.due if tt else float("inf"))
            tpot.append((tt[-1] - tt[0]) / (len(tt) - 1) if len(tt) > 1
                        else 0.0)
        row = {"rate": rate, "requests": len(ttft),
               "ttft_s": ttft, "tpot_s": tpot,
               "ttft_p50_s": stats.percentile(ttft, 50),
               "ttft_p90_s": stats.percentile(ttft, 90),
               "tpot_p50_s": stats.percentile(tpot, 50),
               "tpot_p90_s": stats.percentile(tpot, 90),
               "backlog": ph["backlog"], "drained": ph["drained"],
               "lateness_ms": session.lateness_ms(ph["loop"]),
               "compiles": tally["compiles"] - c0}
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if k not in ("ttft_s", "tpot_s")}), flush=True)
    slo = mix["slo"]
    ttft_lim = slo["ttft_multiple"] * rows[0]["ttft_p90_s"]
    tpot_lim = slo["itl_multiple"] * rows[0]["tpot_p90_s"]
    knee = None
    for row in rows:
        ok = np.mean([a <= ttft_lim and b <= tpot_lim
                      for a, b in zip(row["ttft_s"], row["tpot_s"])])
        b0, b1 = row["backlog"]
        growing = b1 - b0 > max(2, 0.1 * row["requests"])
        row["attainment"], row["growing"] = float(ok), bool(growing)
        print(f"rate {row['rate']}: attainment {ok:.3f}, backlog {b0} -> "
              f"{b1}{' (growing)' if growing else ''}", flush=True)
        if ok >= slo["attainment"] and not growing:
            knee = row["rate"]
    summary = {"config": args.config, "mix": args.mix, "seed": args.seed,
               "seconds": args.seconds, "ttft_limit_s": ttft_lim,
               "tpot_limit_s": tpot_lim, "knee_rps": knee}
    print(json.dumps(summary), flush=True)
    session.write_out(f"sweep-{args.config}-{args.mix}.json",
                      {"summary": summary, "rows": rows})
    return 0


if __name__ == "__main__":
    sys.exit(main())
