#!/usr/bin/env python3
"""The readings a limit of ``traffic/<traffic>.json`` is set from, many seeds in one
process (not a run of the benchmark; the driver never calls it):

    python3 benchmark/prove.py --workload <cell> --seeds 11,12,13 [--queries 1]

For each seed: the cell's data, the cell's query through the cell's session
at the cell's size ``--queries`` times, and two comparisons with the float64
reference over the same files: the program's answers (the lower reading),
and the control, which is the plain reference put in the program's place
and computed in float32, the precision below the one the configuration
states (the upper reading). One JSON line per seed on standard output.
Exits 2 without a TPU, unless ``--rehearse-cpu``.
"""
import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, compare, data, engine, references  # noqa: E402
from benchmark.compile_clock import XlaCompileClock  # noqa: E402
from benchmark.run import make_query, plan_faults  # noqa: E402


def control_gap(cell, root, ref):
    """The control's answer against the reference: (max_rel_err,
    exact_mismatches), as ``compare.answer_gap`` reads a program's answer."""
    low = references.compute(cell.traffic["reference"], root,
                             cell.traffic["columns"], np.float32)
    return compare.answer_gap(low, ref)


def read_seed(cell, seed, queries, clock, scale=None):
    t0 = time.perf_counter()
    root = data.ensure_data(cell.config, list(cell.traffic["columns"]), seed,
                            scale)
    t_data = time.perf_counter() - t0
    sess = engine.open_session(cell.config)
    try:
        df = engine.build_query(sess, root, cell.config, cell.traffic)
        query, plans = make_query(sess, df)
        walls, answers, marks = [], [], [clock.snapshot()]
        fell = engine.host_fallbacks()
        for _ in range(queries):
            t0 = time.perf_counter()
            table, _ = query()
            walls.append(time.perf_counter() - t0)
            answers.append(table.to_pandas())
            marks.append(clock.snapshot())
        faults = plan_faults(plans, cell.config["plan"])
        if engine.host_fallbacks() != fell:
            faults.append("host fallback")
    finally:
        sess.close()
    ref = references.compute(cell.traffic["reference"], root,
                             cell.traffic["columns"])
    verdict = compare.judge(answers, ref, sum(f is not None for f in faults),
                             cell.traffic["limits"])
    c_err, c_wrong = control_gap(cell, root, ref)
    first = {k: marks[1][k] - marks[0][k] for k in marks[0]}
    return {"workload": cell.name, "seed": seed, "data_s": t_data,
            "walls_s": walls, "faults": [f for f in faults if f],
            "first_query_xla": first,
            "later_xla_requests": marks[-1]["xla_compiles"]
            - marks[1]["xla_compiles"],
            "correct": verdict["correct"], "compared": verdict["compared"],
            "control": {"max_rel_err": c_err, "exact_mismatches": c_wrong}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--queries", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--sf", type=float, default=0.01)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    found = jax.devices()
    if (found[0].platform != "tpu" or len(found) < cell.chips) \
            and not args.rehearse_cpu:
        print("prove: no TPU, or fewer chips than the cell asks for",
              file=sys.stderr)
        return 2
    clock = XlaCompileClock()
    engine.build_native_library()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(
            cell, seed, args.queries, clock,
            args.sf if args.rehearse_cpu else None)), flush=True)
    return 2 if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
