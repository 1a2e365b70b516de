"""Readings that the limits on ``correct`` are set from, for one cell at its
own size, many seeds in one process:

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control] [--faults half_batch,altered] \
        [--references float32/float32,bfloat16/bfloat16]

For each seed: the program's compared steps (set-up as a run makes them),
the plain reference, and the numbers compared.  ``--control`` also puts the
reference one precision below the configuration's in the program's place
(the driver's ``control``), and ``--faults`` reruns the program with each
named fault of the driver's ``FAULTS`` planted in the timed step.
``--references`` replays the reference again in each named precision
(operands/result, ``fl_reference.matmul``) and compares the program, the
faults and the control with each of them too (under ``by_reference``).
One JSON line per seed.  The benchmark's own runs do none of this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, lm  # noqa: E402


def numbers(cell, seed, names, prog: dict, ref: dict) -> dict:
    """Every number ``lm.step_checks`` works out, the compared ones among
    them, with the per-leaf norms behind them."""
    key = "loss" if "loss" in ref else "delta_norm"
    r = harness.Run(cell, seed, 0.0, False, 0.0)
    return {**lm.step_checks(r, names, prog, ref, key), "notes": r.notes}


def leaves(x: dict) -> dict:
    return {k: x[k].tolist() for k in ("delta1", "change")}


def readings(cell, seed: int, control: bool, faults: list,
             references: list) -> dict:
    """The program's compared steps (sound, then with each planted fault),
    the reference once (and once more in each extra precision), and the
    control, for one seed."""
    drv = cell.driver()
    progs = {}
    for fault in [None, *faults]:
        run = harness.Run(cell, seed, 0.0, False, time.perf_counter())
        if fault:
            run.wrap = drv.FAULTS[fault]
        t = time.perf_counter()
        st = drv.setup(run)
        drv.free(st)
        progs[fault or "sound"] = (st["prog"], time.perf_counter() - t)
    names = st["model"].leaf_names()
    refs = {}
    t = time.perf_counter()
    refs["cell"] = drv.reference(run, st)
    out = {"seed": seed, "reference_s": time.perf_counter() - t,
           "leaves": names}
    for spec in references:
        ops, res = spec.split("/")
        t = time.perf_counter()
        refs[spec] = drv.reference(run, st, {"operands": ops, "result": res})
        out[f"reference_s.{spec}"] = time.perf_counter() - t
    if control:
        t = time.perf_counter()
        progs["control"] = (drv.control(run, st), time.perf_counter() - t)
    out["reference_leaves"] = {k: leaves(r) for k, r in refs.items()}
    for tag, (prog, secs) in progs.items():
        n = numbers(cell, seed, names, prog, refs["cell"])
        out[tag + "_notes"] = n.pop("notes")
        out[tag] = n
        out[tag + "_s"] = secs
        out[tag + "_leaves"] = leaves(prog)
        for spec in references:
            n = numbers(cell, seed, names, prog, refs[spec])
            n.pop("notes")
            out.setdefault("by_reference", {}).setdefault(spec, {})[tag] = n
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="",
                    help="comma-separated names from the driver's FAULTS")
    ap.add_argument("--references", default="",
                    help="comma-separated operands/result precisions")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: no TPU found")
    from repro.launch.train import init_compile_cache
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for s in args.seeds.split(","):
        print(json.dumps(readings(
            cell, int(s), args.control,
            [f for f in args.faults.split(",") if f],
            [r for r in args.references.split(",") if r])), flush=True)


if __name__ == "__main__":
    main()
