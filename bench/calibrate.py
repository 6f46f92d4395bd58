"""Readings that the limits of ``correct`` are set from (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... [--control]

For each seed, in one process: the cell's own set-up and warm-up segments
(``--seconds 0``: the shortest window), the program's readings against the
reference (the lower readings), and with ``--control`` the same numbers of
the reference put in the program's place and computed at the lower
precision (the control), or with a fault planted in it: ``frozen`` (a
step that leaves the state unchanged) and ``half_batch`` (half of the
rows left out, the mean over the rest). One JSON line per seed, then the
largest program reading and the smallest control and fault readings.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

VARIANTS = {"control": {"quant": "float8_e4m3fn"}, "frozen": {"fault": "frozen"},
            "half_batch": {"fault": "half_batch"}}


def main(argv=None, root: Path = ROOT, devices_for=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    root = Path(root)
    sys.path.insert(0, str(BENCH))
    import run as run_lib

    run_lib.configure(root)

    import jax

    import check
    import costs
    import drive
    import spec as spec_lib

    spec = spec_lib.Spec(root, root / "bench")
    cell = spec.cell(args.workload)
    devices = (devices_for or run_lib.chip_devices)(cell.chips)
    peaks = costs.peaks_for(devices[0].device_kind) if devices_for is None else {}
    with jax.default_matmul_precision("highest"):
        reference = check.reference_for(cell)
        variants = {name: check.reference_for(cell, **kw)
                    for name, kw in VARIANTS.items()} if args.control else {}
    spec_check = cell.traffic["check"]
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        outcome, params = drive.execute(cell, seed, 0.0, None, t, devices, peaks)
        t_ref = time.perf_counter()
        want = check.reference_run(reference, cell, params, outcome)
        row = {"seed": seed, "program": check.readings(spec_check, outcome.program, want),
               "exact": outcome.exact, "reference_s": time.perf_counter() - t_ref}
        for name, variant in variants.items():
            got = check.reference_run(variant, cell, params, outcome)
            row[name] = check.readings(spec_check, got, want)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params, outcome
    summary = {k: max(r["program"][k] for r in rows) for k in rows[0]["program"]}
    out = {"workload": args.workload, "seeds": args.seeds, "program_max": summary}
    if args.control:
        for name in VARIANTS:
            out[f"{name}_min"] = {k: min(r[name][k] for r in rows) for k in summary}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
