"""Run one benchmark cell once on the accelerator and print its result.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the repository root.  The cell's files are found by the names in
``BENCHMARK.json`` (see ``chipbench/harness.py``).  With ``--trace 0`` the
result line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics and the device trace's busy time and breakdown.  The
numbers that decide ``correct`` are printed last on standard error, and
under ``checks`` at the end of the result line, the last line of standard
output.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero before measuring anything.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# TPU runtime logs are off rather than written to a fixed path outside
# the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

TRACE_DIR = ROOT / ".chipbench_trace"


def device_info(chips: int, peaks: dict):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU found (devices: {devs})")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        sys.exit(f"chipbench: no peaks for device kind {kind!r} in "
                 f"chipbench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")["devices"]
    device = device_info(cell.chips, peaks)

    import jax
    from repro.kernels import ops as kops
    from repro.launch.train import init_compile_cache
    init_compile_cache()     # <checkout>/.jax_compile_cache, or the one
    #                          JAX_COMPILATION_CACHE_DIR names
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if kops._interpret():
        sys.exit("chipbench: Pallas kernels would run in interpret mode")

    trace_dir = TRACE_DIR / args.workload if args.trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), T0,
                      trace_dir)
    jax.monitoring.register_event_duration_secs_listener(run.on_duration)
    out = cell.driver().main(run)

    device["memory_peak_bytes"] = run.memory_peak_bytes
    breakdown = None
    if args.trace:
        from chipbench import trace
        tr = trace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = tr["breakdown"]
        inputs = {**out["layer"], "trace": tr, "peaks": peaks[device["kind"]]}
        readers = cell.readers()
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]].read(inputs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**out["end_to_end"], "setup_s": run.setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    print(json.dumps({"setup_s": run.setup_s, **run.notes,
                      "layer": {k: v for k, v in out["layer"].items()
                                if isinstance(v, (int, float))}}),
          file=sys.stderr)
    print(f"compiles_in_window {run.notes['compiles_in_window']}",
          file=sys.stderr)
    harness.print_checks(run)
    print(harness.result_line(run, out, device, metrics, breakdown),
          flush=True)


if __name__ == "__main__":
    main()
