"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Sections (one per paper table/figure + framework-level):
  1. paper tables 1-5 analogues (FF/PFF accuracy + schedule times)
  2. FF vs backprop on the synthetic LM (framework substrate)
  3. kernel validation sweep (Pallas vs oracle, interpret mode)
  3b. kernel autotuner sweep + table smoke (writes BENCH_kernel_tune.json)
  4. roofline table from the dry-run records (if present)
  5. FF hot-loop perf baseline (writes BENCH_ff_hotloop.json)

``--full`` runs the bigger paper-table configuration; default is the
quick profile (~10 min on this CPU container). ``--only=<section>``
selects one section — ``--only=ff_hotloop`` is the ``make bench-smoke``
target. Exits non-zero if any kernel-vs-oracle max error exceeds
``ERR_BUDGET``, if the fused kernel path leaks a separate norm-divide
op into the hand-off jaxpr, or if any argument is unrecognized (a
typo'd ``--only foo`` used to be ignored and silently run EVERY
section) — regressions and operator error both fail loudly in CI.
"""
from __future__ import annotations

import sys
import time

ERR_BUDGET = 1e-4


SECTIONS = ("tables", "lm", "lm_schedules", "lm_negatives", "lm_exec",
            "kernels", "tune", "roofline", "ff_hotloop", "pff_exec",
            "pff_faults", "serve", "trace")


def main(argv):
    full = False
    only = None
    for a in argv:
        if a == "--full":
            full = True
        elif a.startswith("--only="):
            only = a.split("=", 1)[1]
        else:
            # unknown flags (incl. the space form `--only foo`) used to
            # be dropped on the floor and every section would run
            print(f"unknown argument {a!r}; usage: python -m "
                  f"benchmarks.run [--full] "
                  f"[--only=<{'|'.join(SECTIONS)}>]")
            sys.exit(2)
    if only is not None and only not in SECTIONS:
        print(f"unknown --only section {only!r}; "
              f"expected one of {', '.join(SECTIONS)}")
        sys.exit(2)
    from repro.launch import compile_cache
    compile_cache.use_compile_cache()
    t0 = time.time()
    failures = []

    if only in (None, "tables"):
        print("\n##### 1. Paper tables 1-5 analogues #####")
        from benchmarks import paper_tables
        paper_tables.run_tables(quick=not full)

    if only in (None, "lm"):
        print("\n##### 2. FF vs backprop on the synthetic LM #####")
        from benchmarks import lm_ff
        lm_ff.run()

    if only in (None, "lm_schedules"):
        print("\n##### 2b. Joint-FF vs chapter-scheduled FF (paper's "
              "schedule on a transformer) #####")
        from benchmarks import lm_schedules
        lm_schedules.run()

    if only in (None, "lm_negatives"):
        print("\n##### 2c. LM negative-strategy ablation "
              "(random/fixed/adaptive corruption) #####")
        from benchmarks import lm_negatives
        lm_negatives.run()

    if only in (None, "lm_exec"):
        print("\n##### 2d. LM chapters on the real executor: bit-equality"
              " + CE budget on the BPE text source (multi-device) #####")
        from benchmarks import lm_exec
        res = lm_exec.run(quick=not full)
        failures.extend(res["failures"])

    if only in (None, "kernels"):
        print("\n##### 3. Kernel validation (Pallas interpret vs oracle) "
              "#####")
        from benchmarks import kernels as kbench
        worst = kbench.run()
        if worst > ERR_BUDGET:
            failures.append(f"kernel sweep max_err {worst:.2e} > "
                            f"{ERR_BUDGET:.0e}")

    if only in (None, "tune"):
        print("\n##### 3b. Kernel autotuner (measure-many, pick-fastest "
              "+ table smoke) #####")
        from benchmarks import kernels as kbench
        res = kbench.run_tune(quick=not full)
        failures.extend(res["failures"])

    if only in (None, "roofline"):
        print("\n##### 4. Roofline (from dry-run records) #####")
        from benchmarks import roofline
        roofline.main()

    if only in (None, "ff_hotloop"):
        print("\n##### 5. FF hot-loop baseline (ref vs fused) #####")
        from benchmarks import ff_hotloop
        res = ff_hotloop.run(quick=not full)
        if res["max_grad_err"] > ERR_BUDGET:
            failures.append(f"ff_hotloop grad max_err "
                            f"{res['max_grad_err']:.2e} > {ERR_BUDGET:.0e}")
        leaked = res["handoff_norm_divide_ops"]["pallas_fused"]
        if leaked:
            failures.append(
                f"ff_hotloop: {leaked} norm-divide op(s) outside the "
                f"fused kernel in the inter-layer hand-off jaxpr "
                f"(the divide must run in the kernel epilogue)")

    if only in (None, "pff_exec"):
        print("\n##### 6. Real PFF executor: measured vs simulated "
              "(multi-device) #####")
        from benchmarks import pff_exec as pexec_bench
        res = pexec_bench.run(quick=not full)
        failures.extend(res["failures"])

    if only in (None, "pff_faults"):
        print("\n##### 7. Executor resilience: checkpoint overhead + "
              "fault recovery (multi-device) #####")
        from benchmarks import pff_faults
        res = pff_faults.run(quick=not full)
        failures.extend(res["failures"])

    if only in (None, "serve"):
        print("\n##### 8. Serving: continuous batching + live hot-swap "
              "(multi-device) #####")
        from benchmarks import serve as serve_bench
        res = serve_bench.run(quick=not full)
        failures.extend(res["failures"])

    if only in (None, "trace"):
        print("\n##### 9. Observability: traced executor + serve run, "
              "critical-path gates (multi-device) #####")
        from benchmarks import trace as trace_bench
        res = trace_bench.run(quick=not full)
        failures.extend(res["failures"])

    print(f"\nbenchmarks done in {time.time() - t0:.0f}s")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        sys.exit(1)


if __name__ == "__main__":
    main(sys.argv[1:])
