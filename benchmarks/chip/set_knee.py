#!/usr/bin/env python3
"""Write a knee sweep's result into its configuration's file.

    python3 benchmarks/chip/set_knee.py chiprun_out/sweep-<config>-<mix>.json
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main() -> int:
    summary = json.loads(Path(sys.argv[1]).read_text())["summary"]
    if not summary["knee_rps"]:
        print("set_knee.py: the sweep found no rate that meets the limits",
              file=sys.stderr)
        return 1
    path = HERE / "configs" / f"{summary['config']}.json"
    config = json.loads(path.read_text())
    config["knee_rps"] = summary["knee_rps"]
    path.write_text(json.dumps(config, indent=2) + "\n")
    print(f"{path.name}: knee_rps = {summary['knee_rps']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
