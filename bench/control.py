"""The control of a cell's comparison: the plain reference, computed in
bfloat16, put in the program's place. A sound comparison reads it as not
correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Each seed runs as in ``run.py`` (set-up, a window at the cell's own load,
the check), with the control in the program's place, and prints one JSON
line: the control's verdict (``correct``) and readings beside their limits,
and the program's own (``program``), judged the same way. Seeds run one
after another in this process. Needs a TPU, as ``run.py`` does. Exits 0
only if every control reads not correct and every program reads correct.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def verdict(lines) -> int:
    """0 where each control failed its comparison and each program passed
    the same one, else 1."""
    return int(not all(line["correct"] is False
                       and line["program"]["correct"] is True
                       for line in lines))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    t0 = T_START
    lines = []
    for seed in args.seeds:
        try:
            line = harness.run_cell(
                cell, seed, args.seconds, False, t_start=t0, control=True,
                out_dir=harness.ROOT / ".bench_out" / args.workload)
        except harness.Refused as e:
            print(f"control: {e}; no result", file=sys.stderr)
            return 2
        lines.append({k: line[k] for k in ("correct", "checks", "program")})
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **lines[-1]}), flush=True)
        del line
        gc.collect()
        t0 = time.perf_counter()
    return verdict(lines)


if __name__ == "__main__":
    sys.exit(main())
