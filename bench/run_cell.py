"""Benchmark entry point: one run of one cell on the chip it is started on.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json`` (weights drawn on the device
from ``--seed``, every shape its traffic reaches warmed), serves the
traffic for ``--seconds`` seconds, checks the served tokens against the
plain reference, and prints one JSON line as the last line of standard
output: the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics with a profiler trace of the window (``--trace 1``).  Exits 1,
with no result line, where JAX finds no TPU or fewer chips than the cell
asks for.  The compile cache lives in ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_spec(args.workload)
    harness.enable_compile_cache()
    try:
        devices = harness.check_devices(int(spec.cell["chips"]))
    except harness.NoAccelerator as e:
        harness.log(f"FAIL: {e}")
        return 1
    result = harness.run_cell(spec, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
