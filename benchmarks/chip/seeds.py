"""Read a cell's correctness numbers on many seeds in one process, for the
program and for the control, so that set-up is paid once.

    python3 benchmarks/chip/seeds.py --workload <name> --seconds <s> \
        --seeds 1 2 3 ... --control-seeds 4 5 6

Prints one JSON line per seed: ``seed``, ``control``, ``correct``, the
checks and the end-to-end metrics. The limits in ``PERF.md`` were set from
such readings; the benchmark's own runs (``run.py``) never run the control.
"""

import argparse
import json
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    bench.env_for_run()
    bench.enable_cache()
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        if not seeds:
            continue
        session = bench.Session(args.workload, control=control)
        for seed in seeds:
            r = session.run(seed, args.seconds, False)
            print(json.dumps({
                "seed": seed, "control": control, "correct": r["correct"],
                "requests_in_window": r["requests_in_window"],
                "checks": r["checks"],
                "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            }), flush=True)
        del session
    return 0


if __name__ == "__main__":
    sys.exit(main())
