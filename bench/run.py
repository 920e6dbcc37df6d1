"""Command line of the benchmark:

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last on stdout; exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python3 -m bench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness
    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_line(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
