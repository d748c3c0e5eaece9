"""The idle split by program span (``bench.spans``) on hand-built
traces: nested spans, a gap across two spans, gaps outside every span,
two devices, and host events that are not program spans."""
import types

import pytest

from bench import run, spans, tracefile

E = tracefile.Event
WINDOW = (0.0, 110.0)


def _trace():
    host = [E("bench:job", 0, 110), E("fit:sequential", 0, 100),
            E("chapter", 10, 80), E("task:train", 20, 30),
            E("PjitFunction(train_layer_chapter)", 20, 2),
            E("task:wait", 30, 20), E("task:neg_gen", 55, 30),
            E("neg:score", 60, 20)]
    programs = {
        # idle 5-22, 30-50, 60-80, 95-110
        0: [E("a", 0, 5), E("a", 22, 8), E("a", 50, 10), E("a", 80, 15)],
        # idle 85-92 (across chapter and fit:sequential), 105-110
        1: [E("a", 0, 85), E("a", 92, 13)],
    }
    return tracefile.Trace(programs, {}, host)


def _idle_share(trace):
    ctx = types.SimpleNamespace(reduced=tracefile.reduce(trace, WINDOW))
    return run.metric_reader("idle_share")(ctx)


def test_idle_is_attributed_to_the_innermost_program_span():
    got = spans.idle_by_span(_trace(), WINDOW)
    # ns of both devices, halved (the mean over devices), in seconds
    want = {"fit:sequential": (10 + 2) / 2, "chapter": (10 + 5) / 2,
            "task:train": 2 / 2, "task:wait": 20 / 2, "neg:score": 20 / 2,
            "task:neg_gen": 0.0, "": (10 + 5) / 2}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})


def test_layer_shares_and_remainder_add_up_to_idle_share():
    tr = _trace()
    got = spans.shares(spans.idle_by_span(tr, WINDOW), 110e-9)
    assert got == pytest.approx({
        "idle_job_share": 100 * 6 / 110,
        "idle_driver_share": 100 * (7.5 + 0 + 10) / 110,
        "idle_trainer_share": 100 * (1 + 10) / 110,
        "": 100 * 7.5 / 110})
    assert sum(got.values()) == pytest.approx(_idle_share(tr))


def test_a_trace_without_program_spans_reads_none():
    tr = _trace()
    tr.host = [e for e in tr.host if spans.layer_of(e.name) is None]
    got = spans.shares(spans.idle_by_span(tr, WINDOW), 110e-9)
    assert {k: got[k] for k in ("idle_job_share", "idle_driver_share",
                                "idle_trainer_share")} == dict.fromkeys(
        ("idle_job_share", "idle_driver_share", "idle_trainer_share"))
    assert got[""] == pytest.approx(_idle_share(tr))


@pytest.mark.parametrize("name,layer", [
    ("fit:executor", "idle_job_share"), ("fit:init", "idle_job_share"),
    ("chapter:inputs", "idle_driver_share"),
    ("task:handoff", "idle_trainer_share"), ("task:head",
                                             "idle_trainer_share"),
    ("bench:job", None), ("PjitFunction(goodness_class_scores)", None),
    ("task", None)])
def test_layer_of(name, layer):
    assert spans.layer_of(name) == layer
