"""``repro.obs`` — tracing, metrics & critical-path analysis.

The measurement substrate under every wall-clock claim in the repo:

* ``obs.trace`` — thread-safe ``Tracer`` (spans / events / counters on
  one monotonic clock domain) with a ``NOOP`` default that records
  nothing; every lexical span, the ``NOOP``'s too, is also a
  ``jax.profiler.TraceAnnotation`` on the device trace's clock.
* ``obs.export`` — exporter registry (``register_exporter``) with
  Chrome/Perfetto ``trace.json`` and JSONL builtins.
* ``obs.analyze`` — critical path over ``pff_dag.deps``, per-node
  busy/idle, hand-off on/off-critical-path attribution, makespan
  decomposition.

Enable via ``api.fit(..., trace=True)`` / ``api.serve(...,
trace=True)`` (or pass a ``Tracer``), read the handle back from
``FitResult.trace`` / ``ServeResult.trace``, then
``obs.export.export(result.trace, "trace.json")`` and load it in
Perfetto, or ``obs.analyze.analyze(result.trace)``.

``export``/``analyze`` are lazy attributes (PEP 562): importing
``repro.obs`` (which ``checkpoint.py`` does for the ``NOOP`` tracer)
stays as cheap as ``obs.trace`` itself — no registry, no ``pff_dag``,
no jax — until a consumer actually touches them.
"""
import importlib

from repro.obs.trace import NOOP, Tracer, as_tracer          # noqa: F401

_SUBMODULES = ("trace", "export", "analyze")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
