"""Readings that the limits of ``correct`` are set from, one process per seed:

    python3 benchmark/prove.py --workload <name> --seed <n>

One set-up, then the numbers compared: for a sound fit; for the reference put
in the program's place (``reference.VARIANTS``: the fp8 control); and for a fit
with each fault of the traffic's ``FAULTS`` planted in the timed path.  It measures no metric and the benchmark's own runs
never call it.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as runner


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, workload = runner.load_cell(args.workload)
    runner.require_chips(cell["chips"])
    from mmlspark_tpu.core.jit_cache import enable_compile_cache

    enable_compile_cache()
    traffic = importlib.import_module(f"benchmark.traffic.{workload['kind']}")
    state, _ = traffic.setup(cfg, workload, args.seed)
    readings = {}

    def read(name, result, variant=None):
        r = traffic.check(state, result, variant=variant)
        readings[name] = {**{n: v for n, (v, _) in r.items()}, **result["observed"]}
        print(name, readings[name], file=sys.stderr)

    sound = traffic.window(state, 0.0, max_fits=1)
    evaluation = sound.get("evaluation")
    for variant in traffic.reference.VARIANTS:
        sound["evaluation"] = evaluation  # check() takes it off the device once read
        read(variant or "sound", sound, variant)
    hooks = {k: state[k] for fault in traffic.FAULTS.values() for k in fault}
    for name, fault in traffic.FAULTS.items():  # half_batch spends the data set: it is last
        state.update(hooks)
        state.update(fault)
        read(name, traffic.window(state, 0.0, max_fits=1))
    print(json.dumps({"workload": args.workload, "seed": args.seed, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
