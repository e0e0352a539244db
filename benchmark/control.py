"""The control: the plain reference with one guarantee of the
configuration broken, put in the program's place at the cell's own size,
and judged by the harness's own comparison.  It has to come out not
correct.  The benchmark's runs never run it.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

The control is ``control`` in the cell's configuration file, a key of its
reference's ``CONTROLS`` (``reference.py``'s ``fail_open`` answers DENY
where no rule matches).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def control_checks(cell, seed: int) -> dict:
    """The harness's checks with the control's answers served for every
    batch of the pool, each answered in full with status 200."""
    wl = harness.build_workload(cell, seed)
    refs = harness.reference_module(cell.config)
    ref = refs.Reference(wl.tree)
    ctl = refs.CONTROLS[cell.config["control"]](wl.tree)
    calls = [harness.Call(pool=b, t_send=0.0, t_recv=1.0,
                          decisions=harness.expected_codes(refs, ctl, rows),
                          statuses=np.full(len(rows), 200, np.int32))
             for b, rows in enumerate(wl.pool)]
    result = harness.compare_calls(
        calls, [harness.expected_codes(refs, ref, rows) for rows in wl.pool])
    return harness.checks_of(result)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    args = parser.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        checks = control_checks(cell, seed)
        print(json.dumps({"workload": args.workload,
                          "control": cell.config["control"], "seed": seed,
                          "correct": harness.passes(checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
