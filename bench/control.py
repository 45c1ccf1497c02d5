"""Readings that set a cell's correctness limits: the program's, the control's
and the planted faults'.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, in one process: one run of the cell as the benchmark makes
it (set-up, window, served tokens, cached spikes at the close), then the
plain reference over them.  The program is judged as the benchmark judges
it; so are, each in the program's place, the control (the reference
computed one precision below the configuration's,
``reference.numerics.control_of``) and, for an int8 control, the two
planted faults that confine int8 to part of the stack
(``reference.numerics.PARTIAL``).  Each is judged against the cell's
limits: the control has to come out not correct.  Prints one JSON line
per seed; exits 1 without a TPU.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.reference.numerics import PARTIAL, control_of

    spec = harness.load_spec(args.workload)
    harness.enable_compile_cache()
    try:
        devices = harness.check_devices(int(spec.cell["chips"]))
    except harness.NoAccelerator as e:
        harness.log(f"FAIL: {e}")
        return 1
    control = control_of(spec.config)
    others = (control,) + (PARTIAL if control == "int8" else ())
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.run_cell(spec, seed=seed, seconds=args.seconds, trace=False,
                             t_start=t0, devices=devices, others=others)
        print(json.dumps({"seed": seed, "correct": r["correct"], "checks": r["checks"],
                          "control": control, "judged": r["judged"],
                          "metrics": r["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
