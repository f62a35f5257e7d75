#!/usr/bin/env python3
"""Set a configuration's check limits from the readings of
``check_readings.py``.

    python3 benchmarks/chip/set_limits.py chiprun_out/readings-<cell>.json

For every number with a control reading: the lower reading is the
largest the program gave over the seeds, the upper the smallest the
control gave. Where the upper is at least three times the lower, the
limit lies between them, at lower^0.4 * upper^0.6: more room above the
lower reading (fresh seeds read higher than a dozen did) than below the
upper. A number without such an upper reading separates nothing and is
not compared: it leaves the configuration's checks. Numbers the readings
do not hold (references left out with ``--parts``) keep their limits. The
script fails when the control fails no number. Exact checks (limit 0) are
not listed.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def limit(lower: float, upper: float) -> float:
    if lower <= 0:
        return upper / 3.0
    return lower ** 0.4 * upper ** 0.6


def main() -> int:
    data = json.loads(Path(sys.argv[1]).read_text())
    cell = data["workload"]
    config_name = cell.split(".")[0]
    path = HERE / "configs" / f"{config_name}.json"
    config = json.loads(path.read_text())
    checks = dict(config.get("checks", {}))
    separated = False
    for name, s in data["summary"].items():
        lo, up = s["program_max"], s["control_min"]
        if up is None or lo == up == 0:
            continue                        # an exact check, limit 0
        if up < 3 * lo:
            print(f"{name}: lower {lo!r}, upper {up!r}: no upper reading "
                  "three times the lower; not compared")
            checks.pop(name, None)
            continue
        separated = True
        checks[name] = round(limit(lo, up), 6)
        print(f"{name}: lower {lo!r}, upper {up!r} -> limit {checks[name]}")
    if not separated:
        print("set_limits.py: the control fails no number", file=sys.stderr)
        return 1
    config["checks"] = checks
    path.write_text(json.dumps(config, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
