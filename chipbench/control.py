"""Read the output check's control on the chip: run a cell (usually with a
short window, at the cell's own load) with the float8 control in the
program's place: at the positions where the program served a token, the
gap of the token that the control ranks first is held to the
configuration's limit, and the harness's verdict (``correct``) has to
come out false.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

One process for all seeds; one JSON line per seed on standard output.
The benchmark's own runs never run the control.
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
    args = ap.parse_args(argv)

    import jax

    from harness import spec

    run.use_compile_cache(jax)
    cell = spec.load_cell(run.ROOT, run.BENCH_DIR, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.log("chipbench control: needs a TPU")
        return 2
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices[0],
                           control=True)
        print(json.dumps({"seed": seed, "checks": res["checks"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
