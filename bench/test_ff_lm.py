"""The LM chapter family (``bench/families/ff_lm.py``) on the CPU: its
configuration file against the registered DeepSeek-V2-Lite, its
counts, its token source, and whole runs at a tiny width, sound and
with the timed path broken underneath (``correct`` must come out
false)."""
import dataclasses

import jax
import pytest

from bench import compiles, peaks, run

CELL = "dsv2lite.seq4k_1chip"
TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "kv_lora_rank": 16, "qk_nope_head_dim": 8,
              "qk_rope_head_dim": 8, "v_head_dim": 8,
              "num_attention_heads": 2, "num_key_value_heads": 2,
              "moe_intermediate_size": 32, "router_experts": 8,
              "n_routed_experts": 4, "expert_shard": 1,
              "num_experts_per_tok": 2, "vocab_size": 256}


def tiny_cell():
    cell = run.find_cell(CELL)
    return dataclasses.replace(
        cell, config={**cell.config, **TINY_MODEL},
        traffic={**cell.traffic, "seq": 16})


def test_configuration_is_the_registered_model_cut_to_one_chip():
    """Every published number of the file is the registered config's,
    but the three cuts (depth, experts held, vocabulary slice)."""
    from repro.configs import get_config
    cell = run.find_cell(CELL)
    fam = run.family(cell)
    got = fam.model_config(cell.model)
    want = get_config("deepseek-v2-lite")
    assert got.groups == ((("mla_dense",), 1), (("mla_moe",), 4))
    assert (got.moe.held, got.moe.num_experts) == (8, 64)
    assert got.vocab == want.vocab // 8
    assert got.mla == want.mla and got.rope_scaling == want.rope_scaling
    assert dataclasses.replace(got.moe, held=0) == want.moe
    for f in ("d_model", "n_heads", "n_kv", "d_ff", "rope_theta",
              "norm_eps", "tie_embeddings"):
        assert getattr(got, f) == getattr(want, f), f
    assert set(cell.config["reduced"]) == {"num_hidden_layers",
                                           "n_routed_experts", "vocab_size"}


def test_counts():
    cell = run.find_cell(CELL)
    fam = run.family(cell)
    m, t = cell.model, cell.traffic
    assert fam.samples_per_job(m, t) == 2 * 2 * 2 * 4096
    assert fam.layer_steps_per_job(m, t) == 2 * 2 * 5
    assert fam.job_kernel_calls(m, t) == []
    # the dense block's forward over one block step's 16,384 tokens:
    # 13.8 M attention and 67.2 M MLP parameters, twice a token, and
    # attention's causal half
    dense = fam._block_flops(m, "mla_dense", 16384, 4, 4096)
    proj = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert dense == pytest.approx(
        2 * 16384 * (proj + 3 * 2048 * 10944)
        + 2 * 4 * 16 * 4096 * 4096 / 2 * (192 + 128))
    assert fam.job_model_flops(m, t) > 0


def test_token_source_is_a_pure_function_of_its_seeds():
    fam = run.family(run.find_cell(CELL))
    src = fam.ZipfTokens(12800, 32, 1.1, 2 ** 31 + 7)
    a = src.blocks("train", 4, seed=3)
    assert a.shape == (4, 33) and a.min() >= 0 and a.max() < 12800
    assert (a == src.blocks("train", 4, seed=3)).all()
    assert not (a == src.blocks("train", 4, seed=4)).all()
    assert not (a == fam.ZipfTokens(12800, 32, 1.1, 7).blocks(
        "train", 4, seed=3)).all()
    # Zipf(1.1): id 0 is drawn about 1 / H(12800, 1.1) of the time
    big = src.blocks("val", 64, seed=0)
    assert 0.05 < (big == 0).mean() < 0.15


def test_another_familys_cell_is_refused():
    fam = run.family(run.find_cell(CELL))
    cell = run.find_cell(CELL)
    with pytest.raises(ValueError, match="layer_sizes"):
        fam.validate({**cell.model, "layer_sizes": [784, 64]},
                     cell.traffic)


def go(cell, seed=2 ** 31 + 3):
    return run.run_cell(cell, seed, 0.1, False, jax.devices()[:1],
                        compiles.CompileMeter(), t_start=0.0,
                        peak=peaks.peak_for("TPU v5 lite"))


def test_tiny_run_is_correct():
    res = go(tiny_cell())
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(tiny_cell().limits)
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}


@pytest.fixture
def patched(monkeypatch):
    yield monkeypatch
    monkeypatch.undo()
    jax.clear_caches()


def fault_unchanged_state(mp):
    """The block step hands back the block's state unchanged."""
    from repro import optim
    mp.setattr(optim, "adam_update", lambda p, g, st, **kw: (p, st))


def fault_renorm_topk(mp):
    """The MoE layer renormalises its top-k gates, whatever the
    configuration says."""
    from repro.models import mlp
    orig = mlp._router
    mp.setattr(mlp, "_router", lambda p, x, moe: orig(
        p, x, dataclasses.replace(moe, norm_topk=True)))


def fault_plain_rope(mp):
    """MLA rotates by the plain rope frequencies, without YaRN."""
    from repro.models import common
    orig = common.apply_rope
    mp.setattr(common, "apply_rope",
               lambda x, pos, theta, scaling=None: orig(x, pos, theta))


def fault_half_batch(mp):
    """Every block and head step trains on half of its batch."""
    from repro.core import pff_lm
    orig = pff_lm.chapter_batches

    def half(source, *, batch, steps):
        tasks = orig(source, batch=batch, steps=steps)
        return lambda c, k: ({"tokens": b["tokens"][:batch // 2]}
                             for b in tasks(c, k))
    mp.setattr(pff_lm, "chapter_batches", half)


@pytest.mark.parametrize("fault", [fault_unchanged_state, fault_renorm_topk,
                                   fault_plain_rope, fault_half_batch])
def test_broken_timed_path_is_not_correct(fault, patched):
    jax.clear_caches()
    fault(patched)
    res = go(tiny_cell())
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == res["attempted"] > 0
