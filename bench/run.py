"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are named in
``BENCHMARK.json`` at the root of the checkout (see ``bench/spec.py``).
The run makes its weights and its stream from ``--seed``, warms up every
shape the window uses, measures for about ``--seconds``, then compares
what the timed path reported for the rounds that the traffic's ``check``
names with the plain reference (``bench/check.py``). With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are reported instead of the end-to-end ones; they read the trace
as ``bench/trace_reduce.py`` reduces it, with the program's scopes,
kernels and host spans by name from ``bench/trace_scopes.py`` beside it.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (platform, kind, count,
peak memory; with ``--trace 1`` also the device's busy and window
seconds), with ``--trace 1`` a ``breakdown``, and last ``checks``: each
compared number beside its limit, also the last lines of standard error.
Without a TPU, with fewer chips than the cell asks for, or without the
program (``src/repro``) in the checkout, the run exits non-zero and prints
no result.

JAX's persistent compilation cache lives at ``.bench_out/jax_cache/`` in
the checkout, so only a checkout's first run of a cell compiles.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# what run.trace takes from bench/trace_scopes.summarize
SCOPED = ("scopes", "model_s", "state_s", "other_s", "kernels", "boundary_s", "switch_stall_s")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def chip_devices(chips: int):
    """The chips this cell runs on; exits when JAX sees no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX sees {devices[0].platform}); refusing to run")
    if len(devices) < chips:
        sys.exit(f"bench: the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def configure(root: Path) -> None:
    """Before JAX is imported: its persistent compilation cache at a fixed
    directory of this checkout that only the benchmark writes, every
    program cached (the eager ones too, which compile in under a second),
    nothing evicted; and the program and the benchmark importable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".bench_out" / "jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for p in (str(root / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None, root: Path = ROOT, devices_for=chip_devices, peaks=None) -> int:
    args = parse(argv)
    root = Path(root)
    if not (root / "src" / "repro").is_dir():
        sys.exit(f"bench: no program under {root / 'src'}; refusing to run")
    configure(root)

    import check
    import costs
    import drive
    import spec as spec_lib

    spec = spec_lib.Spec(root, root / "bench")
    cell = spec.cell(args.workload)
    devices = devices_for(cell.chips)
    dev = devices[0]
    peaks = peaks or costs.peaks_for(dev.device_kind)
    trace_dir = root / ".bench_out" / "trace" if args.trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)

    outcome, params = drive.execute(cell, args.seed, args.seconds, trace_dir, T0, devices, peaks)
    run = outcome.run
    log(f"window {run.window_s:.3f} s, {run.window_rounds} rounds in "
        f"{len(run.window_segments)} segments; set-up {run.setup_s:.3f} s; "
        f"stream generator {1e3 * run.gen_s_per_round:.3f} ms per round")
    log("segment seconds " + " ".join(f"{x:.3f}" for x in run.segment_s))
    if trace_dir is not None:
        import trace_reduce
        import trace_scopes

        t_trace = time.perf_counter()
        path = trace_reduce.latest_xplane(str(trace_dir))
        window = {"is_engine": drive.is_engine_module,
                  "rounds_per_run": int(cell.traffic["segment_rounds"]),
                  "skip_runs": drive.TRACE_SKIP_RUNS[cell.traffic["runner"]]}
        run.trace = trace_reduce.summarize(trace_reduce.load(path, drive.is_kernel_op), **window)
        scoped = trace_scopes.summarize(trace_scopes.read(path), **window)
        if run.trace is not None and scoped is not None:
            run.trace.update({k: scoped[k] for k in SCOPED})
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduction {time.perf_counter() - t_trace:.3f} s")

    t_check = time.perf_counter()
    values = check.compare(cell, params, outcome)
    del params
    log(f"reference check {time.perf_counter() - t_check:.3f} s")
    values.update(outcome.exact)
    limits = dict(cell.stated["limits"])
    limits.update({k: 0.0 for k in outcome.exact})
    correct, checks = check.judge(values, limits)

    metrics = spec_lib.read_metrics(
        spec, cell.per_layer if args.trace else cell.end_to_end, run)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": run.peak_bytes}
    result = {"correct": correct, "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
