"""``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell in this one process; the last line of
standard output is the result. Exits non-zero, with no result, where JAX
finds no TPU of a known kind or fewer chips than the cell asks for."""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the control and the fault tests put another entry in the program's place
    ap.add_argument("--entry", default=None)
    args = ap.parse_args(argv)
    from chipbench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), entry_name=args.entry)
    except (harness.Refused, ModuleNotFoundError) as e:
        # no chip, an unknown name, or no program beside the benchmark
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
