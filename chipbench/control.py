"""Read the two readings the output check's limit is set from, on the
chip, in one process: the program's own gap on some seeds (the lower
reading), as the benchmark runs them, and the float8 control's on
others (the upper reading). The control runs the cell (usually with a
short window, at the cell's own load) with the float8 control in the
program's place: at the positions where the program served a token,
the gap of the token that the control ranks first is held to the
configuration's limit, and the harness's verdict (``correct``) has to
come out false.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--program-seeds <n> ...] [--traced-seeds <n> ...]

The program's seeds run first, for the benchmark's ``run_seconds``
(``--traced-seeds`` with the profiler on, as ``--trace 1`` runs them),
then the control's for ``--seconds``. One JSON line per run on standard
output. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from harness import spec

    run.use_compile_cache(jax)
    cell = spec.load_cell(run.ROOT, run.BENCH_DIR, args.workload)
    seconds = spec.load_benchmark(run.ROOT)["run_seconds"]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.log("chipbench control: needs a TPU")
        return 2
    runs = ([("program", s, seconds, False, False)
             for s in args.program_seeds]
            + [("traced", s, seconds, True, False)
               for s in args.traced_seeds]
            + [("control", s, args.seconds, False, True)
               for s in args.seeds])
    for kind, seed, secs, trace, control in runs:
        res = run.run_cell(cell, seed, secs, trace, devices[0],
                           control=control)
        print(json.dumps({"seed": seed, "run": kind,
                          "checks": res["checks"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
