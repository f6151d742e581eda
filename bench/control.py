"""The control: the check must refuse the reference computed one precision
below what the deployment states.

    python3 bench/control.py --workload covid-rf100.batch --seconds 5 \\
        --seeds 1 2 3

For each seed, one window of the cell runs at its own load and size; then
the check runs twice on the same sampled requests: once on what the program
served, once with the reference's float32 answers (``reference.control``:
energy, and the forest's soft vote, in float32) in the program's place.  The
first must pass and the second fail.  The benchmark's own runs never run
this; ``bench/tests/test_control.py`` runs it at a size the CPU holds.
"""
import argparse
import json
import sys
import time

import run


def control_numbers(cell, seeds, seconds, devices) -> list[dict]:
    import check
    import deploy
    out = []
    for seed in seeds:
        result, program, window = run.run_cell(
            cell, seed, seconds, False, t_start=time.perf_counter(),
            devices=devices)
        dep = deploy.build(cell.config)
        ref = deploy.reference(dep)
        ctl = check.run_check(window.rec, ref, dep.X_test,
                              cell.config["check_sample"], seed,
                              answers=ref.control)
        out.append({"seed": seed, "program": program,
                    "program_correct": check.verdict(program),
                    "control": ctl, "control_correct": check.verdict(ctl)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, trace=False)
    devices = run.require_chips(cell.chips)
    import repro
    repro.enable_compile_cache()
    rows = control_numbers(cell, args.seeds, args.seconds, devices)
    for r in rows:
        print(json.dumps(r), flush=True)
    ok = all(r["program_correct"] and not r["control_correct"] for r in rows)
    print(json.dumps({"workload": args.workload, "control_refused": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
