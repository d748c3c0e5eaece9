"""Roofline table builder — reads the dry-run JSONs and prints/saves the
per-(arch x shape x mesh) three-term roofline analysis (deliverable g).

Also exports the single-kernel roofline helpers the autotuner's bench
gate uses (``benchmarks.kernels.run_tune``): an analytic min-time for
one fused ff_dense fwd+bwd step against nominal platform peaks, so
tuning wins are reported as %-of-roofline per shape, not just raw
seconds (the load-insensitive framing — raw seconds on this shared CPU
container are scheduling noise, and interpret-mode Pallas numbers are
not kernel numbers at all; the % column says how far from the machine's
ceiling the MEASURED winner is, whatever the machine).
"""
from __future__ import annotations

import json
import os

NOTE = {
    "compute": "more chips / higher MXU occupancy moves this",
    "memory": "fusion + bf16 activations cut HBM traffic",
    "collective": "resharding or larger per-device batch cuts ICI bytes",
}

# Nominal (peak_flops/s, peak_bytes/s) per ``jax.Device.device_kind``
# for the kernel-tune %-of-roofline column. "TPU v5 lite" (v5e): bf16
# MXU peak + HBM bandwidth from Google Cloud's "TPU v5e" page; "cpu": a
# round-number container-class estimate (2 cores x AVX2 FMA, DDR). A
# device with no row is an error, never a default.
PEAKS = {
    "TPU v5 lite": (1.97e14, 8.19e11),
    "cpu": (1.0e11, 2.0e10),
}


def ff_dense_roofline(M, K, N, *, device_kind, dtype_bytes=4):
    """Analytic roofline for ONE fused ff_dense fwd + fused-bwd step
    (what the autotuner times): flops/bytes totals, the compute and
    memory terms, and the max-of-terms min time in seconds."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    # fwd: matmul 2MKN + bias/relu/square-accumulate ~3MN
    # bwd: dy rebuild ~4MN + the dw product 2MKN (the step differentiates
    # w only, so the dx kernel is dead code)
    flops = 2 * (2 * M * K * N) + 7 * M * N
    # fused-path HBM traffic: x, w, b in; y, g out (fwd) + x, y, cots in;
    # dw, db out (bwd) — activations never round-trip inside a step
    bytes_ = dtype_bytes * (2 * (M * K + K * N) + 3 * M * N
                            + 2 * N + 3 * M)
    peak_f, peak_b = PEAKS[device_kind]
    t_compute = flops / peak_f
    t_memory = bytes_ / peak_b
    return {
        "flops": flops, "bytes": bytes_,
        "compute_term_s": t_compute, "memory_term_s": t_memory,
        "roof_s": max(t_compute, t_memory),
        "bound": "compute" if t_compute >= t_memory else "memory",
    }


def pct_of_roofline(measured_s, roof_s):
    """Measured time as % of the analytic ceiling (100 = at the roof;
    interpret-mode numbers land far below 1 by design)."""
    if not measured_s or measured_s <= 0:
        return 0.0
    return 100.0 * roof_s / measured_s


def load_records(dirpath="experiments/dryrun"):
    recs = []
    if not os.path.isdir(dirpath):
        return recs
    for fn in sorted(os.listdir(dirpath)):
        if fn.endswith(".json"):
            with open(os.path.join(dirpath, fn)) as f:
                recs.append(json.load(f))
    return recs


def fmt_row(r):
    terms = {"compute": r["compute_term_s"], "memory": r["memory_term_s"],
             "collective": r["collective_term_s"]}
    dom = max(terms, key=terms.get)
    util = r.get("flops_utilization", 0.0)
    return (f"| {r['arch']:24s} | {r['shape']:11s} "
            f"| {'2x16x16' if r['multi_pod'] else '16x16':7s} "
            f"| {terms['compute']:9.4f} | {terms['memory']:9.4f} "
            f"| {terms['collective']:10.4f} | {dom:10s} | {util:5.2f} |")


def print_table(recs, multi_pod=None):
    print("| arch | shape | mesh | compute_s | memory_s | "
          "collective_s | bottleneck | MF/HF |")
    print("|---|---|---|---|---|---|---|---|")
    for r in recs:
        if multi_pod is not None and r["multi_pod"] != multi_pod:
            continue
        print(fmt_row(r))


def main():
    recs = load_records()
    if not recs:
        # not silently empty: say exactly how to produce the records
        print("no dry-run records under experiments/dryrun — generate "
              "them first with:\n"
              "  PYTHONPATH=src python -m repro.launch.dryrun\n"
              "then re-run this section for the per-arch roofline "
              "table.")
        return
    n1 = sum(1 for r in recs if not r["multi_pod"])
    n2 = sum(1 for r in recs if r["multi_pod"])
    print(f"# Roofline ({n1} single-pod + {n2} multi-pod records)\n")
    print("## Single-pod (16x16 = 256 chips)")
    print_table(recs, multi_pod=False)
    if n2:
        print("\n## Multi-pod (2x16x16 = 512 chips)")
        print_table(recs, multi_pod=True)
    # bottleneck census
    census = {}
    for r in recs:
        if r["multi_pod"]:
            continue
        census[r["bottleneck"]] = census.get(r["bottleneck"], 0) + 1
    print("\nbottleneck census (single-pod):", census)


if __name__ == "__main__":
    main()
