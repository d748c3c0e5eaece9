"""Kernel registry + autotuner: registry round-trip and custom-impl
registration (mirroring the api strategy-registry tests one level
down), tuning-table persistence (save -> load -> memo hit, byte-stable
ordering), candidate generation under the VMEM row-residency budget,
oracle-gate rejection, deterministic winners under an injected timer,
and the poisoned-table fallback paths.

The conftest autouse fixture points REPRO_TUNE_TABLE at a per-test tmp
file, so these tests never see (or pollute) a real ~/.cache table.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref, registry
from repro.kernels.ff_dense import VMEM_BUDGET_BYTES, vmem_block_bytes


def _fake_timer(times):
    """Deterministic injectable timer: label-keyed lookup with a
    default, never calls the thunk (so tests time nothing)."""
    def timer(thunk, label):
        del thunk
        for frag, t in times.items():
            if frag in label:
                return t
        return 1.0
    return timer


def _tune_once(shapes=((16, 64, 128),), norms=(False,), times=None,
               **kw):
    return autotune.tune_ff_dense(
        list(shapes), norms=norms, timer=_fake_timer(times or {}),
        save=True, verbose=False, **kw)


# ---------------------------------------------------------------------------
# Registry round-trip + custom impl registration (the strategy-registry
# contract, one level down)
# ---------------------------------------------------------------------------

def test_registry_round_trip_of_builtin_impl_names():
    for op, reg in registry.REGISTRIES.items():
        assert set(reg.names()) >= {"pallas", "ref"}
        assert reg.choices()[0] == "auto"
        for name in reg.names():
            assert reg.get(name).name == name
            assert name in reg
        assert list(iter(reg)) == sorted(reg.names())
        assert registry.registry(op) is reg


def test_registry_unknown_name_lists_choices():
    with pytest.raises(ValueError, match="pallas"):
        registry.ff_dense.get("does_not_exist")
    with pytest.raises(ValueError, match="flash_attention"):
        registry.flash_attention.get("nope")
    with pytest.raises(ValueError, match="unknown op"):
        registry.registry("not_an_op")


def test_registry_rejects_auto_as_impl_name():
    with pytest.raises(ValueError, match="auto"):
        registry.ff_dense.register("auto", lambda *a, **k: None)


def test_register_custom_ff_dense_impl(key):
    """A user-registered impl is reachable through ops.ff_dense(impl=)
    and shows up in the live FF_DENSE_IMPLS choices."""
    def shifted(x, w, b, *, norm, interpret, blocks):
        y, g = ref.ff_dense_ref(x, w, b)
        return y + 1.0, g

    registry.register_kernel_impl("ff_dense", "shifted", shifted)
    try:
        assert "shifted" in registry.ff_dense
        assert "shifted" in ops.FF_DENSE_IMPLS
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(key, (16, 32)) * 0.1
        b = jnp.zeros((32,))
        y, _ = ops.ff_dense(x, w, b, impl="shifted")
        yr, _ = ref.ff_dense_ref(x, w, b)
        np.testing.assert_allclose(y, yr + 1.0, rtol=1e-6)
        with pytest.raises(ValueError, match="already registered"):
            registry.register_kernel_impl("ff_dense", "shifted", shifted)
        registry.register_kernel_impl("ff_dense", "shifted", shifted,
                                      overwrite=True)
    finally:
        registry.ff_dense.unregister("shifted")
    assert "shifted" not in registry.ff_dense
    assert "shifted" not in ops.FF_DENSE_IMPLS


def test_auto_resolution_prefers_platform_then_fallback():
    reg = registry.KernelRegistry("demo", fallback="ref")
    reg.register("fast", lambda: None,
                 preferred=lambda p: p == "tpu")
    reg.register("ref", lambda: None)
    assert reg.resolve("tpu").name == "fast"
    assert reg.resolve("cpu").name == "ref"


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------

def test_candidate_blocks_clamped_aligned_and_within_budget():
    M, K, N = 48, 512, 384
    for norm in (False, True):
        grid = autotune.candidate_blocks(M, K, N, norm=norm)
        assert grid, "empty candidate grid for a modest shape"
        for bm, bn in grid:
            assert bm <= M
            assert bn % 128 == 0 or bn == N
            assert vmem_block_bytes(K, N, bm, bn, norm=norm) \
                <= VMEM_BUDGET_BYTES


def test_candidate_blocks_norm_respects_row_residency():
    """norm=True widens the y block to the whole (bm, N) row, so a
    shape whose row cannot fit must lose its biggest bm candidates."""
    # small K keeps the x/w blocks cheap, large N makes the norm path's
    # whole-row y block (bm x N) the binding constraint
    M, K, N = 256, 256, 8192
    plain = autotune.candidate_blocks(M, K, N, norm=False)
    normed = autotune.candidate_blocks(M, K, N, norm=True)
    assert set(normed) <= set(plain)
    assert max(bm for bm, _ in normed) < max(bm for bm, _ in plain)


# ---------------------------------------------------------------------------
# Table persistence + memoization
# ---------------------------------------------------------------------------

def test_table_round_trip_and_memo_hit():
    rows = _tune_once(times={"ref": 0.5, "bm=16": 0.1})
    assert rows and rows[0]["winner"] is not None
    path = autotune.table_path()
    assert os.path.exists(path)

    fresh = autotune.TuneTable.open(path)
    assert len(fresh) == 1
    assert fresh.entries == {r["key"]: r["winner"] for r in rows}

    autotune.invalidate_cache()
    loads0 = autotune.STATS["loads"]
    hits0 = autotune.STATS["memo_hits"]
    r = rows[0]
    first = autotune.lookup("ff_dense", r["M"], r["K"], r["N"],
                            jnp.float32, jax.default_backend())
    again = autotune.lookup("ff_dense", r["M"], r["K"], r["N"],
                            jnp.float32, jax.default_backend())
    assert first == again == fresh.entries[r["key"]]
    assert autotune.STATS["loads"] == loads0 + 1
    assert autotune.STATS["memo_hits"] == hits0 + 1


def test_table_save_is_byte_stable_across_insertion_order(tmp_path):
    e1 = {"impl": "ref", "time_s": 0.5, "err": 0.0, "grad_err": 0.0}
    e2 = {"impl": "pallas", "bm": 16, "bn": 128, "time_s": 0.1,
          "err": 1e-6, "grad_err": 1e-6}
    a = autotune.TuneTable(str(tmp_path / "a.json"))
    a.put("k1", dict(e1))
    a.put("k2", dict(e2))
    b = autotune.TuneTable(str(tmp_path / "b.json"))
    b.put("k2", dict(e2))
    b.put("k1", dict(e1))
    a.save()
    b.save()
    with open(a.path, "rb") as f1, open(b.path, "rb") as f2:
        assert f1.read() == f2.read()


def test_retune_with_same_inputs_leaves_file_bit_identical():
    _tune_once(times={"ref": 0.5})
    with open(autotune.table_path(), "rb") as f:
        before = f.read()
    _tune_once(times={"ref": 0.5})
    with open(autotune.table_path(), "rb") as f:
        assert f.read() == before


# ---------------------------------------------------------------------------
# Winner selection
# ---------------------------------------------------------------------------

def test_deterministic_winner_under_fake_timer():
    times = {"bm=16|bn=128": 0.01, "ref": 0.2}
    rows_a = _tune_once(times=times)
    autotune.invalidate_cache()
    rows_b = _tune_once(times=times)
    assert rows_a[0]["winner"] == rows_b[0]["winner"]
    w = rows_a[0]["winner"]
    assert w["impl"] == "pallas"
    assert (w["bm"], w["bn"]) == (16, 128)
    assert w["err"] <= autotune.ERR_GATE
    assert w["grad_err"] <= autotune.ERR_GATE


def test_candidate_rejected_on_oracle_error_breach(monkeypatch):
    """The fastest candidate must NOT win if it breaches the 1e-4 gate
    — fast-but-wrong never reaches the table."""
    bad = (16, 128, None)
    real_errors = autotune._candidate_errors

    def rigged(impl_name, blocks, data, oracle, *, norm, interpret):
        if blocks == bad:
            return 1.0, 1.0           # grossly wrong
        return real_errors(impl_name, blocks, data, oracle, norm=norm,
                           interpret=interpret)

    monkeypatch.setattr(autotune, "_candidate_errors", rigged)
    # the rigged candidate is also by far the fastest
    rows = _tune_once(times={"bm=16|bn=128": 1e-9, "ref": 0.2})
    w = rows[0]["winner"]
    assert w is not None
    assert not ("bm" in w and (w["bm"], w["bn"]) == (16, 128))
    breaches = [rj for rj in rows[0]["rejected"]
                if tuple(rj["blocks"] or ()) == bad[:2] + (None,)
                or rj["blocks"] == list(bad)]
    assert any("oracle error breach" in rj["reason"]
               for rj in rows[0]["rejected"])
    assert breaches or rows[0]["n_rejected"] >= 1


def test_untuned_bucket_warns_when_nothing_passes(monkeypatch):
    monkeypatch.setattr(autotune, "_candidate_errors",
                        lambda *a, **k: (1.0, 1.0))
    with pytest.warns(UserWarning, match="no candidate passed"):
        rows = _tune_once()
    assert rows[0]["winner"] is None
    assert len(autotune.TuneTable.open(autotune.table_path())) == 0


# ---------------------------------------------------------------------------
# ops integration: the table steers "auto" and blocks reach "pallas"
# ---------------------------------------------------------------------------

def _put_entry(key, entry):
    t = autotune.TuneTable.open()
    t.put(key, entry)
    t.save()


def test_lookup_steers_auto_to_table_winner(key):
    """A persisted winner redirects impl='auto' — observed through a
    sentinel impl with a distinctive output."""
    M, K, N = 8, 16, 32

    def sentinel(x, w, b, *, norm, interpret, blocks):
        y, g = ref.ff_dense_ref(x, w, b)
        return y + 7.0, g

    registry.register_kernel_impl("ff_dense", "sentinel", sentinel)
    try:
        _put_entry(
            autotune.key_for("ff_dense", M, K, N, jnp.float32,
                             jax.default_backend(), False),
            {"impl": "sentinel", "time_s": 0.1, "err": 0.0,
             "grad_err": 0.0})
        x = jax.random.normal(key, (M, K))
        w = jax.random.normal(key, (K, N)) * 0.1
        b = jnp.zeros((N,))
        y, _ = ops.ff_dense(x, w, b, impl="auto")
        yr, _ = ref.ff_dense_ref(x, w, b)
        np.testing.assert_allclose(y, yr + 7.0, rtol=1e-6)
        # other shape buckets miss the table -> registry default (ref
        # on CPU), no sentinel shift
        y2, _ = ops.ff_dense(x[:4], w, b, impl="auto")
        np.testing.assert_allclose(y2, ref.ff_dense_ref(x[:4], w, b)[0],
                                   rtol=1e-6)
    finally:
        registry.ff_dense.unregister("sentinel")
        autotune.invalidate_cache()


def test_tuned_blocks_reach_forced_pallas(key):
    """impl='pallas' consults the table for block shapes even when the
    recorded WINNER is another impl."""
    M, K, N = 16, 64, 128
    _put_entry(
        autotune.key_for("ff_dense", M, K, N, jnp.float32,
                         jax.default_backend(), False),
        {"impl": "ref", "time_s": 0.1, "err": 0.0, "grad_err": 0.0,
         "bm": 8, "bn": 128, "pallas_time_s": 0.2})
    seen = {}
    builtin = registry.ff_dense.get("pallas")
    orig = builtin.fn

    def spy(x, w, b, **kw):
        seen["blocks"] = kw["blocks"]
        return orig(x, w, b, **kw)

    registry.register_kernel_impl("ff_dense", "pallas", spy,
                                  tunable=True, overwrite=True)
    try:
        x = jax.random.normal(key, (M, K))
        w = jax.random.normal(key, (K, N)) * 0.1
        b = jnp.zeros((N,))
        y, g = ops.ff_dense(x, w, b, impl="pallas")
        assert seen["blocks"] == (8, 128, None)
        yr, gr = ref.ff_dense_ref(x, w, b)
        np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)
    finally:    # the builtin back whole: "auto" prefers it on a TPU
        registry.register_kernel_impl("ff_dense", "pallas", orig,
                                      preferred=builtin.preferred,
                                      tunable=True, overwrite=True)


# ---------------------------------------------------------------------------
# Poisoned-table fallbacks: warn and default, never crash
# ---------------------------------------------------------------------------

def _lookup_small():
    return autotune.lookup("ff_dense", 8, 16, 32, jnp.float32,
                           jax.default_backend())


def test_corrupt_json_file_warns_and_defaults(key):
    path = autotune.table_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("{ not json at all")
    with pytest.warns(UserWarning, match="poisoned kernel tuning table"):
        assert _lookup_small() is None
    # dispatch still works end-to-end on defaults
    x = jax.random.normal(key, (8, 16))
    w = jax.random.normal(key, (16, 32)) * 0.1
    b = jnp.zeros((32,))
    y, g = ops.ff_dense(x, w, b)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("entry", [
    {"impl": "pallas", "time_s": 0.1, "err": 0.0, "grad_err": 0.0,
     "bm": "sixteen", "bn": 128},                    # non-int block
    {"impl": "pallas", "time_s": 0.1, "err": 0.0, "grad_err": 0.0,
     "bm": 1 << 20, "bn": 1 << 20},                  # breaks residency
    {"impl": "pallas", "time_s": 0.1, "err": 0.0, "grad_err": 0.0},
    # ^ pallas winner without blocks
    {"impl": "not_registered", "time_s": 0.1, "err": 0.0,
     "grad_err": 0.0},                               # unknown impl
    {"time_s": 0.1},                                 # no impl at all
])
def test_poisoned_entry_warns_and_defaults(entry, key):
    _put_entry(autotune.key_for("ff_dense", 8, 16, 32, jnp.float32,
                                jax.default_backend(), False), entry)
    with pytest.warns(UserWarning, match="poisoned tuning-table entry"):
        assert _lookup_small() is None
    with pytest.warns(UserWarning, match="poisoned tuning-table entry"):
        x = jax.random.normal(key, (8, 16))
        w = jax.random.normal(key, (16, 32)) * 0.1
        b = jnp.zeros((32,))
        y, _ = ops.ff_dense(x, w, b, impl="auto")
    np.testing.assert_allclose(y, ref.ff_dense_ref(x, w, b)[0],
                               rtol=1e-6)


def test_key_for_is_stable_and_bucketed():
    k = autotune.key_for("ff_dense", 64, 128, 256, jnp.float32, "cpu",
                         True)
    assert k == "ff_dense|M=64|K=128|N=256|dtype=float32|platform=cpu|norm=1"
    assert k != autotune.key_for("ff_dense", 64, 128, 256, jnp.float32,
                                 "cpu", False)
    assert k != autotune.key_for("ff_dense", 64, 128, 256, jnp.bfloat16,
                                 "cpu", True)


def test_table_meta_documents_bit_exactness_policy():
    """The meta note is load-bearing documentation: it must pin the
    oracle-gate-not-bit-exactness policy and the matrix's ref pin."""
    rows = _tune_once(times={"ref": 0.1})
    assert rows
    with open(autotune.table_path()) as f:
        raw = json.load(f)
    note = raw["meta"]["note"]
    assert "bit-exactness" in note and "ref" in note
    assert raw["meta"]["err_gate"] == autotune.ERR_GATE
