"""One process, one cell, once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds a TPU with as many chips as the cell asks for, or exits non-zero and
prints no result: there is no CPU fallback and no switch for one.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def find_chips(chips: int) -> tuple:
    """The TPU devices and the last line's ``device`` object; ``SystemExit``
    where JAX finds another platform or fewer chips than the cell needs."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"no accelerator: {exc}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(
            f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices, {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = parser.parse_args(argv)

    from benchmark import harness, program

    spec = harness.load_spec(ns.workload)
    program.enable_compile_cache()
    devices, device = find_chips(int(spec.cell["chips"]))
    trace_dir = os.path.join(TRACE_DIR, ns.workload)
    result = harness.run_cell(
        spec, ns.seed, ns.seconds, bool(ns.trace), PROCESS_START, device,
        devices=devices, trace_dir=trace_dir)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
