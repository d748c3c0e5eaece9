"""Executor resilience under deterministic faults: cost + correctness.

Measures what the fault-tolerance layer (``repro.core.faults`` +
``PFFExecutor(resilience=...)``) actually costs and proves what it
promises, writing ``BENCH_pff_faults.json`` (``make fault-smoke``):

  1. checkpoint overhead — the all_layers N=4 run with chapter-granular
     manifests on vs off (warm caches both ways): total and per-chapter
     checkpoint seconds (the device->host drain + atomic .npz write),
     then a resume from the last manifest gated BIT-EXACT against the
     uninterrupted sequential weight stream.
  2. per-fault recovery cost — one warm all_layers N=4 run per named
     plan (crash_once / delay_node / drop_handoff / corrupt_handoff /
     dead_node): makespan delta vs the fault-free run, retry /
     reassignment / hand-off counters, and the bit-exactness gate (every
     one of these recovery paths must reproduce the fault-free weight
     stream — that is the point of entry-time crash injection, version/
     integrity-gated hand-off and device reassignment).
  3. kill-then-resume — for each schedule in {all_layers, single_layer,
     federated} a SUBPROCESS run is hard-killed mid-chapter
     (``os._exit`` via the ``kill_mid`` plan, exit code
     ``faults.KILL_EXIT``), then a second subprocess resumes from the
     surviving manifests; the resumed process itself gates its final
     weights bit-exact against the fault-free reference (the
     ``pff_exec`` CLI's ``--fault-plan``/``--resume-from`` path — the
     same one ``tests/test_pff_faults.py`` drives). These children are a
     CPU correctness gate: they start with ``JAX_PLATFORMS=cpu`` and the
     4-fake-device flag set explicitly, because on a TPU host this
     parent process already holds the chip and a child reaching for it
     would fail or hang.

Needs >= 4 devices (export
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax is
imported; this module sets it when imported first, and ``make
fault-smoke`` always does). With fewer devices an existing
``BENCH_pff_faults.json`` is kept rather than clobbered — same policy
as ``benchmarks/pff_exec.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

if "jax" not in sys.modules:                       # pragma: no cover
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax

from repro import api, data as data_lib
from repro.configs.ff_mlp import FFMLPConfig
from repro.core import faults, pff_exec

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    os.pardir, "src")

FAULT_ROWS = ("crash_once", "delay_node", "drop_handoff",
              "corrupt_handoff", "dead_node")
KILL_SCHEDULES = (("all_layers", 4), ("single_layer", 2),
                  ("federated", 4))


def _fit(cfg, task, devices, *, resilience=None, resume_from=None):
    return api.fit(cfg, task, backend="executor", schedule="all_layers",
                   num_nodes=4, devices=devices, resilience=resilience,
                   resume_from=resume_from)


def _bit_gate(label, ref, res, failures):
    ok = pff_exec.params_bit_equal(ref.params, res.params)
    if not ok:
        failures.append(f"{label}: weight stream diverged from the "
                        "fault-free reference")
    return ok


def _kill_resume_row(schedule, nodes, splits, n_train, failures):
    """Hard-kill a CLI run mid-chapter, resume it, parse the verdicts."""
    env = dict(os.environ)
    # the parent holds the accelerator (if any): keep the children on
    # the CPU with 4 faked devices
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    base = [sys.executable, "-m", "repro.core.pff_exec",
            "--schedule", schedule, "--nodes", str(nodes),
            "--splits", str(splits), "--n-train", str(n_train)]
    row = {"schedule": schedule, "nodes": nodes}
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        killed = subprocess.run(
            base + ["--fault-plan", "kill_mid", "--checkpoint-dir", td],
            capture_output=True, text=True, env=env, timeout=540)
        row["killed_s"] = time.perf_counter() - t0
        row["kill_exit"] = killed.returncode
        if killed.returncode != faults.KILL_EXIT:
            failures.append(
                f"kill-resume {schedule}: expected the injected kill "
                f"(exit {faults.KILL_EXIT}), got {killed.returncode}:\n"
                f"{killed.stdout}\n{killed.stderr}")
            return row
        manifests = sorted(os.listdir(td))
        row["manifests_at_kill"] = manifests
        if not manifests:
            failures.append(f"kill-resume {schedule}: no chapter "
                            "manifest survived the kill")
            return row
        t0 = time.perf_counter()
        resumed = subprocess.run(
            base + ["--resume-from", td], capture_output=True, text=True,
            env=env, timeout=540)
        row["resume_s"] = time.perf_counter() - t0
        row["resume_exit"] = resumed.returncode
        # the resumed CLI gates params_bit_equal vs the fault-free
        # reference itself and exits non-zero on divergence
        row["resume_bit_exact"] = resumed.returncode == 0
        if resumed.returncode != 0:
            failures.append(
                f"kill-resume {schedule}: resumed run failed or "
                f"diverged (exit {resumed.returncode}):\n"
                f"{resumed.stdout}\n{resumed.stderr}")
    return row


def run(quick=True, out_path=None):
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "BENCH_pff_faults.json")
    splits, n_train = (4, 520) if quick else (8, 1000)
    cfg = FFMLPConfig(layer_sizes=(784, 128, 128), epochs=splits * 2,
                      splits=splits, neg_mode="random",
                      classifier="goodness", goodness_fn="sumsq",
                      batch_size=64, seed=0)
    task = data_lib.mnist_like(n_train=n_train, n_test=200)
    devices = jax.devices()
    n_dev = len(devices)
    print(f"devices: {n_dev} x {devices[0].platform}")
    results = {
        "config": {"n_train": n_train, "splits": splits,
                   "layer_sizes": list(cfg.layer_sizes),
                   "backend": jax.default_backend(), "devices": n_dev,
                   "cpu_count": os.cpu_count()},
        "failures": [],
    }
    if n_dev < 4:
        msg = (f"needs 4 devices, found {n_dev} — set XLA_FLAGS="
               "--xla_force_host_platform_device_count=4 "
               "(see make fault-smoke)")
        print(msg)
        if os.path.exists(out_path):
            print(f"keeping existing {os.path.normpath(out_path)}")
        else:
            results["note"] = msg
            with open(out_path, "w") as f:
                json.dump(results, f, indent=2)
        return results
    failures = results["failures"]

    ref = api.fit(cfg, task, backend="sequential")
    _fit(cfg, task, devices)                      # compile warm-up
    base = _fit(cfg, task, devices)               # warm fault-free run
    _bit_gate("baseline", ref, base, failures)
    print(f"fault-free all_layers N=4 makespan {base.makespan:.2f}s "
          f"acc {base.test_acc:.4f}")

    # ---- 1. checkpoint overhead + resume --------------------------------
    with tempfile.TemporaryDirectory() as td:
        rc = faults.ResilienceConfig(checkpoint_dir=td, keep_last=splits)
        _fit(cfg, task, devices, resilience=rc)   # warm (incl. writes)
        ck = _fit(cfg, task, devices, resilience=rc)
        st = ck.resilience
        _bit_gate("checkpointing", ref, ck, failures)
        # resume from the second-newest manifest so the final chapter is
        # actually REPLAYED through the DAG (not just restored)
        resumed = _fit(cfg, task, devices, resume_from=os.path.join(
            td, f"pff_chapter_{splits - 2:04d}.npz"))
        _bit_gate("resume", ref, resumed, failures)
        if resumed.resilience["resumed_from_chapter"] != splits - 2:
            failures.append("resume restored the wrong manifest")
        results["checkpoint"] = {
            "makespan_s_off": base.makespan,
            "makespan_s_on": ck.makespan,
            "overhead_s": ck.makespan - base.makespan,
            "checkpoints_written": st["checkpoints_written"],
            "checkpoint_time_s": st["checkpoint_time_s"],
            "checkpoint_time_s_per_chapter":
                st["checkpoint_time_s"] / max(st["checkpoints_written"], 1),
            "restore_time_s": resumed.resilience["restore_time_s"],
        }
        print(f"checkpointing: +{results['checkpoint']['overhead_s']:.2f}s"
              f" wall ({st['checkpoints_written']} manifests, "
              f"{st['checkpoint_time_s']:.2f}s in save, restore "
              f"{results['checkpoint']['restore_time_s']:.3f}s)")

    # ---- 2. per-fault recovery cost -------------------------------------
    results["faults"] = []
    for name in FAULT_ROWS:
        plan = faults.named_plan(name, splits=splits,
                                 n_layers=len(cfg.layer_sizes) - 1,
                                 num_nodes=4)
        rc = faults.ResilienceConfig(fault_plan=plan,
                                     backoff_base_s=0.01)
        res = _fit(cfg, task, devices, resilience=rc)
        st = res.resilience
        bit = _bit_gate(f"fault {name}", ref, res, failures)
        row = {"plan": name, "makespan_s": res.makespan,
               "recovery_cost_s": res.makespan - base.makespan,
               "retries": st["retries"],
               "reassignments": st["reassignments"],
               "dead_nodes": st["dead_nodes"],
               "recovery_time_s": st["recovery_time_s"],
               "faults_injected": st["faults_injected"],
               "handoff": res.raw.handoff,
               "weights_bit_exact": bit}
        results["faults"].append(row)
        print(f"{name:>16}: makespan {res.makespan:6.2f}s "
              f"(+{row['recovery_cost_s']:5.2f}s) "
              f"injected={st['faults_injected']} "
              + ("bit-exact" if bit else "DIVERGED"))

    # ---- 3. kill mid-chapter, resume, bit-exact (subprocess pairs) ------
    results["kill_resume"] = []
    for schedule, nodes in KILL_SCHEDULES:
        row = _kill_resume_row(schedule, nodes, splits, n_train, failures)
        results["kill_resume"].append(row)
        print(f"kill+resume {schedule:>13} N={nodes}: "
              f"kill_exit={row.get('kill_exit')} "
              f"resume_exit={row.get('resume_exit', '-')} "
              f"manifests={len(row.get('manifests_at_kill', []))} "
              + ("bit-exact" if row.get("resume_bit_exact") else "FAIL"))

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {os.path.normpath(out_path)}")
    return results
