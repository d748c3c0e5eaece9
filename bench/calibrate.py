"""Readings that set a cell's comparison limits, on the chip at the
cell's own size. The benchmark's runs never run this.

    python3 -m bench.calibrate --workload <name> --seeds 11,12,... [--controls 3]
        [--variants control,half_batch,...]

For every seed: the program's check job, as a run's set-up drives it,
against the reference (the sound readings: the lower end of a limit).
For the first ``--controls`` seeds also, each variant of the cell's
family (``bench/families/<family>.py``) put in the program's place and
compared with the reference the same way; the FF-MLP family's:

- ``control``: the reference computed one precision step below the
  configuration's, three bfloat16 passes per product;
- ``half_batch``: the reference with half of every batch left out;
- ``handoff_unnormed``: the reference whose hand-off between layers
  leaves out the length normalisation (the norm epilogue broken);
- ``no_exchange`` (cells on several nodes): the reference with the
  hand-off between nodes left out;
- ``handoff_bf16``: the reference with its hand-off alone at three
  bfloat16 passes (a lower precision in the norm-epilogue path only);
- ``nudged`` (no fault: the look at how round-off grows): the
  reference with every initial weight nudged by one part in 1e7.

A state returned unchanged reads exactly 1 on every per-unit number and
needs no run. One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run


def readings(cell, seed, devices, *, controls: bool, program=True,
             only=None, log=run.log):
    """{variant: compared numbers} for one seed, through the cell's
    family. Without ``program`` only the variants are read (against the
    reference), which needs one chip whatever the cell's count."""
    fam = run.family(cell)
    built = fam.build(cell, seed, devices)
    t0 = time.perf_counter()
    prog = fam.check_job(built) if program else None
    t1 = time.perf_counter()
    names = [v for v in fam.variants(cell) if controls
             and (not only or v in only)]
    out = fam.calibration_readings(cell, seed, built, prog, names)
    log(f"seed {seed}: program check job {t1 - t0:.3f}s, reference and "
        f"{len(names)} variants {time.perf_counter() - t1:.3f}s")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--no-program", action="store_true",
                    help="read only the control and the faults (one chip)")
    ap.add_argument("--variants", default="",
                    help="comma-separated variants to read (default: all)")
    args = ap.parse_args(argv)
    cell = run.find_cell(args.workload)
    fam = run.family(cell)
    looks = ("program",) + fam.SOUND    # sound runs: their largest reading
    run.environment()
    devices = run.tpu_devices(1 if args.no_program
                              else cell.traffic["chips"])
    devices = devices * (cell.traffic.get("num_nodes", 1) // len(devices)
                         or 1)
    seeds = [int(s) for s in args.seeds.split(",")]
    worst = {}
    for i, seed in enumerate(seeds):
        for variant, got in readings(cell, seed, devices,
                                     controls=i < args.controls,
                                     program=not args.no_program,
                                     only=[v for v in args.variants.split(",")
                                           if v]).items():
            row = {"workload": args.workload, "seed": seed,
                   "variant": variant,
                   **{k: got[k] for k in fam.NAMES + ("accuracy",)
                      if k in got}}
            print(json.dumps(row), flush=True)
            w = worst.setdefault(variant, {})
            for k in fam.NAMES:
                if k in got:
                    w[k] = (max if variant in looks else min)(
                        w.get(k, got[k]), got[k])
    print(json.dumps({"workload": args.workload,
                      "max_of_sound_min_of_others": worst}),
          flush=True)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
