"""Read the control (or a planted fault) on the chip at a cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 25 [--fault accept_all]

One process runs the cell once per seed with the fault planted under the
timed path, and prints each run's compared numbers.  It exits 0 only when
every run came out not correct.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="accept_all", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    caught = []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            with faults.planted(args.fault):
                info, result = run.run_cell(args.workload, seed, args.seconds,
                                            False, t0=time.perf_counter())
            caught.append(not result["correct"])
            print(json.dumps({"fault": args.fault, "seed": seed,
                              "requests": info["requests"],
                              "tampered": info["tampered"],
                              "correct": result["correct"],
                              "checks": result["checks"]}), flush=True)
    finally:
        from lighthouse_tpu import device_pipeline

        device_pipeline.shutdown()
    return 0 if caught and all(caught) else 1


if __name__ == "__main__":
    sys.exit(main())
