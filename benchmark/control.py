"""The control of `correct`: the cell's own run with one guarantee of
its configuration broken, which has to come out as not correct.

    python3 -m benchmark.control --workload <name> --fault <name>
        --seeds 3 [--seconds 12] [--rehearse] [--held]

Runs the cell once per seed with the fault planted (benchmark/faults.py)
and prints each run's `correct` and the numbers compared.  Exit 0 only
if every run came out not correct.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

from benchmark import faults, run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9001)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--held", action="store_true")
    args = ap.parse_args()
    caught = 0
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        argv = ["--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", "0"] + \
            (["--rehearse"] if args.rehearse else []) + \
            (["--held"] if args.held else [])
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            code = run.main(argv, faults.FAULTS[args.fault]())
        lines = [ln for ln in buf.getvalue().splitlines()
                 if ln.startswith("{")]
        if code != 0 or not lines:
            # a control that crashes or gives no number has failed
            print(f"control {args.fault} seed {seed}: no result "
                  f"(exit {code}); counts as not correct\n"
                  + buf.getvalue()[-2000:])
            caught += 1
            continue
        line = json.loads(lines[-1])
        print(f"control {args.fault} seed {seed}: correct="
              f"{line['correct']} failed={line['failed']} compared="
              f"{json.dumps(line['compared'])}", flush=True)
        caught += not line["correct"]
    print(f"control {args.fault}: {caught} of {args.seeds} runs came out "
          "not correct")
    return 0 if caught == args.seeds else 1


if __name__ == "__main__":
    sys.exit(main())
