"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. Set-up (imports, TPU start, plan, schedule,
compile or cache load, warm-up) is timed from process start; then requests
run back to back for ``--seconds``; then a seeded sample of them is checked
against the plain reference. With ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics, ``busy_s``,
``window_s`` and a ``breakdown``; with ``--trace 0`` it carries the
end-to-end metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ..., ``checks`` last);
the last lines of standard error are the numbers compared, each beside its
limit. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

``--control`` puts the reference, computed one precision lower, in the
program's place: such a run must come out not correct.
``--dump-trace PATH`` also writes a few requests of the trace to ``PATH``
(``.json.gz``) for the tests of the trace reduction.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--dump-trace")
    args = ap.parse_args(argv)

    bench.env_for_run()
    bench.enable_cache()
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    try:
        session = bench.Session(args.workload, control=args.control,
                                t_process=T_PROCESS)
    except bench.NoChip as e:
        log(f"error: {e}")
        return 2
    log(f"set-up: {json.dumps(session.setup)}")
    result = session.run(args.seed, args.seconds, bool(args.trace),
                         args.dump_trace)
    log(f"requests in window: {result['requests_in_window']}, "
        f"compiles in window: {result['compiles_in_window']}")
    for line in bench.format_checks(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
