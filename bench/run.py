"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It builds the cell's network with ``repro.compile``, serves it with
``Program.serve()``, warms every batch width, drives the cell's traffic for
``--seconds`` and checks every delivered token against the plain reference.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the window.
The last line of standard output is one JSON object; the numbers compared
come last on standard error and under ``checks`` in that object.

It exits nonzero, and prints no result, unless JAX's first device is a TPU:
it never falls back to the CPU.  JAX's compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at
the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"{cell.name} needs {cell.chips} TPU chip(s), found "
                    f"{devices}: the benchmark runs only on the chip")
        return 2
    from repro.runtime import compile_cache

    harness.log(f"compilation cache: "
                f"{compile_cache.enable(ROOT / '.jax_cache')}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, platform="tpu")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
