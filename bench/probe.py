#!/usr/bin/env python3
"""Several runs of one cell in one process: rate sweeps and limit readings.

    python3 bench/probe.py --workload <cell> --seeds 11,12,13 --seconds 40 \
        [--rates 1,2,4] [--traffic <file.json>] [--control] [--trace 0|1] \
        [--chips 4] [--controller-args '{"shards": 16}'] [--out <file.jsonl>]

Each (rate, seed) pair is one ``bench/run.py`` run, in this process, so the
chip is reached and the programs are compiled once.  ``--rates`` scales the
traffic file's reading rate (every process's ``report_period_s`` divided by
the factor); arcs and surges keep their periods; ``--traffic`` tries
another traffic file in the cell's place.  ``--chips`` and
``--controller-args`` run the cell on that many chips (under a mesh) with
that controller (the configuration's ``controller_args``; where they name
``shards``, without the configuration's region latency budget, which a
sharded full pass does not keep): a trial of a cell that ``BENCHMARK.json``
does not hold, metrics chosen as for the cell named.  ``--control`` also reads the check's control (the reference's
objective in bfloat16 in the device's place) on the same applied passes.
Each run prints one JSON line with the seed, the rate, the result and the
numbers compared.  This is a measuring tool: no benchmark run calls it, and
its runs after the first pay no compilation.
"""

from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402


def scaled(traffic: dict, rate: float) -> dict:
    out = copy.deepcopy(traffic)
    for proc in out["processes"]:
        if "report_period_s" in proc:
            proc["report_period_s"] /= rate
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="1")
    ap.add_argument("--traffic", help="a traffic file to use in place of the cell's")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--chips", type=int, help="run the cell on this many chips")
    ap.add_argument("--controller-args", help="JSON object: the configuration's controller_args")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    _, cell, config, traffic = bench_run.load_cell(args.workload)
    if args.traffic:
        traffic = json.loads(pathlib.Path(args.traffic).read_text())
    if args.chips:
        cell = dict(cell, chips=args.chips)
    if args.controller_args:
        config = dict(config, controller_args=json.loads(args.controller_args))
        if config["controller_args"].get("shards"):
            # a sharded full pass keeps no region budget (run.sharded_full_passes)
            config.pop("region_latency_budget_ms", None)
    out = open(args.out, "a") if args.out else None
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            for seed in [int(s) for s in args.seeds.split(",")]:
                t0 = time.time()
                result = bench_run.run(args.workload, seed, args.seconds, bool(args.trace),
                                       control=args.control, traffic=scaled(traffic, rate),
                                       cell=cell, config=config, t_process=t0)
                line = json.dumps({"workload": args.workload, "seed": seed, "rate": rate,
                                   "wall_s": time.time() - t0, **result})
                print(line, flush=True)
                if out is not None:
                    out.write(line + "\n")
                    out.flush()
    except bench_run.NoChip as e:
        print(f"probe: {e}", file=sys.stderr)
        return 2
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
