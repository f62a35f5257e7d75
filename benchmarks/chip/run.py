#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's routed pool (configuration file) with weights made on
the device from ``--seed``, warms every step shape the cell's traffic can
reach, serves the traffic mix as an open loop until the pool holds a
steady load (all of that is ``setup_s``), measures ``--seconds``, and
checks what the window served against the plain float32 references. With
``--trace 1`` the window runs under the profiler and the cell's per-layer
metrics are reported instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each compared number beside its limit. The
checks are also the last lines of standard error. With no TPU, or fewer
chips than the cell asks for, the run exits with code 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout. Every program
    is kept, however fast it compiled."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"run.py: no TPU found (JAX backend is {backend!r}); the "
              "benchmark runs only on a TPU", file=sys.stderr)
        return 2
    if len(jax.devices()) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    from chipbench import cell as cellrun
    print(f"compile cache: {enable_cache()}", flush=True)
    tally = cellrun.compile_counter()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    out = cellrun.serve_cell(cell, args.seed, args.seconds,
                             bool(args.trace), T_START, tally)
    checks = cellrun.run_checks(cell, out, args.seed)
    line = cellrun.result(cell, out, bool(args.trace), checks)
    print(f"total {time.monotonic() - T_START:.1f} s", flush=True)
    cellrun.print_checks(checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
