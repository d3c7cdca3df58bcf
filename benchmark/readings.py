"""The readings a limit is set from, at the cell's own size, several seeds in
one process:

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 [--steps 2] [--control] [--faults]

For every seed: the data, the program's build, ``--steps`` steps of the timed
call (each one's seconds are printed: whether the seed changes the work) and
the last one's gaps to the plain reference (a lower reading); with
``--control`` the gaps of the reference put in the program's place in
bfloat16, and with ``--faults`` those of every fault the kind plants (upper
readings). Every reading is judged by the harness's own ``judge`` against the
cell's limits. One JSON line a reading, each naming the device it was read on.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--steps", type=int, default=1)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--faults", action="store_true")
    ns = parser.parse_args(argv)

    import jax

    from benchmark import harness, program

    spec = harness.load_spec(ns.workload)
    program.enable_compile_cache()
    kind = harness.load_kind(spec.workload["kind"])
    device = jax.devices()[0]
    limits = spec.workload["limits"]

    def report(seed, what, outputs, **more):
        checks = kind.verify(state, outputs, limits)
        print(json.dumps(dict({
            "workload": ns.workload, "seed": seed, "reading": what,
            "device": [device.platform, device.device_kind],
            "correct": harness.judge(checks),
            "gaps": {name: value for name, value, _ in checks},
            "iterations": outputs.get("iterations")}, **more)), flush=True)

    for seed in (int(s) for s in ns.seeds.split(",")):
        state = kind.build(spec.config, spec.workload, seed,
                           harness.Phases())
        step_s = []
        for _ in range(ns.steps):
            t0 = time.perf_counter()
            outputs = kind.step(state)
            step_s.append(time.perf_counter() - t0)
        planted = {name: fault(state) for name, fault in kind.FAULTS.items()
                   } if ns.faults else {}
        kind.release(state)
        gc.collect()
        report(seed, "program", outputs, step_s=step_s)
        if ns.control:
            report(seed, "control", kind.control(state))
        for name, out in planted.items():
            report(seed, "fault." + name, out)
        del state, outputs, planted
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
