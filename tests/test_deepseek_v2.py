"""DeepSeek-V2-Lite's blocks against the plain reference
(``models/ref_deepseek_v2.py``) at a reduced size on seeded random
weights: MLA forward and prefill + decode through the latent cache, the
MoE layer's held share of the experts, routing that drops nothing, and
one chapter of ``pff_lm.train_chapters`` on the two-group stack (a
dense block, then MoE blocks)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import data as data_lib
from repro.configs import get_config
from repro.core import pff_lm
from repro.models import blocks, mlp, ref_deepseek_v2 as ref, transformer

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
TOL = 2e-5        # f32 on the CPU, the same sums in another order


@pytest.fixture(scope="module")
def cfg():
    c = get_config("deepseek-v2-lite").reduced()
    return dataclasses.replace(
        c, num_layers=3, groups=((("mla_dense",), 1), (("mla_moe",), 2)),
        moe=dataclasses.replace(c.moe, num_experts=8, top_k=3, held=4,
                                shard=1))


def _block(cfg, kind, seed=0):
    return blocks.block_init(jax.random.PRNGKey(seed), cfg, kind)


@pytest.mark.parametrize("kind", ["mla_dense", "mla_moe"])
def test_block_forward_matches_reference(cfg, kind):
    p = _block(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = blocks.block_apply(p, cfg, kind, x, {"causal": True})
    y_ref, aux_ref = ref.block(p, x, ref.hparams(cfg), {})
    np.testing.assert_allclose(y, y_ref, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(aux, aux_ref, rtol=TOL, atol=TOL)


def test_prefill_and_decode_through_latent_cache(cfg):
    """Prefill S tokens, then decode two more one at a time through the
    latent cache: each output equals the reference's full forward."""
    p = _block(cfg, "mla_moe")
    S = 12
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S + 2, cfg.d_model))
    full, _ = ref.block(p, x, ref.hparams(cfg), {})
    y, cache = blocks.block_prefill(p, cfg, "mla_moe", x[:, :S],
                                    {"max_len": S + 2})
    np.testing.assert_allclose(y, full[:, :S], rtol=TOL, atol=TOL)
    assert cache["c_kv"].shape[-1] == cfg.mla.kv_lora_rank
    for t in (S, S + 1):
        y, cache = blocks.block_decode(p, cfg, "mla_moe", cache, x[:, t],
                                       jnp.int32(t), {})
        np.testing.assert_allclose(y, full[:, t], rtol=TOL, atol=TOL)


def test_yarn_frequencies_and_scale(cfg):
    """The program's YaRN blend, cos/sin factor and softmax scale are
    the reference's, and differ from plain rope (the config's factor
    40 moves the low frequencies)."""
    from repro.models import common, mla
    y = cfg.rope_scaling
    pos = jnp.arange(40)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 2, 16))
    got = common.apply_rope(x, pos, cfg.rope_theta, y)
    np.testing.assert_allclose(got, ref.rope(x, pos, ref.hparams(cfg)),
                               rtol=TOL, atol=TOL)
    assert not np.allclose(got, common.apply_rope(x, pos, cfg.rope_theta))
    assert mla.softmax_scale(cfg) == pytest.approx(
        ref.softmax_scale(ref.hparams(cfg)))
    assert mla.softmax_scale(cfg) == pytest.approx(
        48 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)


def test_held_shares_add_up_to_the_uncut_layer(cfg):
    """Over every shard, the held experts' parts, with the shared
    expert counted once, add up to what the uncut reference layer gives
    for the whole layer."""
    whole = dataclasses.replace(cfg.moe, held=0, shard=0)
    p = mlp.moe_init(jax.random.PRNGKey(4), whole, cfg.d_model, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    held = cfg.moe.held
    shards = whole.num_experts // held
    total = 0.0
    for s in range(shards):
        part = {**p, **{n: p[n][s * held:(s + 1) * held]
                        for n in ("wi", "wg", "wo")}}
        y, _ = mlp.moe_apply(part, x, dataclasses.replace(cfg.moe, shard=s),
                             cfg.act)
        total = total + y
    shared = mlp.dense_mlp_apply(p["shared"], x, cfg.act)
    hp = {**ref.hparams(cfg), "held": whole.num_experts, "shard": 0}
    y_ref, _ = ref.moe(p, x.reshape(-1, cfg.d_model), hp)
    np.testing.assert_allclose(total - (shards - 1) * shared,
                               y_ref.reshape(x.shape), rtol=TOL, atol=TOL)


def test_routing_every_token_to_one_expert_drops_nothing(cfg):
    """A router that sends every token's first choice to one held
    expert: the layer still computes every assignment (its counts say
    so) and equals the reference, where a capacity of 1.25 would drop
    most of them."""
    p = mlp.moe_init(jax.random.PRNGKey(6), cfg.moe, cfg.d_model,
                     jnp.float32)
    e = cfg.moe.shard * cfg.moe.held          # this shard's first expert
    p["router"] = p["router"].at[:, e].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7),
                                  (2, 32, cfg.d_model)))
    stats = []
    y, _ = mlp.moe_apply(p, x, cfg.moe, cfg.act, stats=stats)
    assert int(stats[0][0]) == x.shape[0] * x.shape[1]
    hp = ref.hparams(cfg)
    y_ref, _ = ref.moe(p, x.reshape(-1, cfg.d_model), hp)
    np.testing.assert_allclose(y, y_ref.reshape(x.shape), rtol=TOL,
                               atol=TOL)
    dropped, _ = ref.moe(p, x.reshape(-1, cfg.d_model), hp,
                         capacity_factor=1.25)
    assert not np.allclose(dropped, y_ref, rtol=1e-3, atol=1e-3)


def test_initial_state_is_the_published_layout(cfg):
    """The state the chapter path starts from (``pff_lm.init_state``,
    which the benchmark's reference starts from too) against
    DeepSeek-V2-Lite's published layout, written out here from the
    published numbers: at the benchmark's cut (5 blocks, 8 of 64
    experts held, an eighth of the vocabulary) every leaf's shape,
    traced without allocating; at the reduced width every norm weight
    0 (the (1 + w) form of the published 1), every matrix drawn at
    1/sqrt of its input width, and no two held experts or blocks
    alike."""
    c = get_config("deepseek-v2-lite")
    cut = dataclasses.replace(
        c, num_layers=5, groups=((("mla_dense",), 1), (("mla_moe",), 4)),
        vocab=102400 // 8, dtype="float32",
        moe=dataclasses.replace(c.moe, held=8, shard=3))
    d, H, r, E, f = 2048, 16, 512, 8, 1408
    attn = {"wq": (d, H, 128 + 64), "wkv_a": (d, r + 64), "kv_norm": (r,),
            "wkv_b": (r, H, 128 + 128), "wo": (H, 128, d)}
    dense = {"wi": (d, 10944), "wg": (d, 10944), "wo": (10944, d)}
    moe = {"router": (d, 64), "wi": (E, d, f), "wg": (E, d, f),
           "wo": (E, f, d),
           "shared": {"wi": (d, 2 * f), "wg": (d, 2 * f), "wo": (2 * f, d)}}
    block = lambda m: ({"norm1": (d,), "attn": attn, "norm2": (d,),
                        "mlp": m},)
    got = jax.eval_shape(lambda: pff_lm.init_state(cut, 0))
    assert {a.dtype for a in jax.tree.leaves(got)} == {np.dtype("float32")}
    shapes = jax.tree.map(lambda a: a.shape, got)
    assert shapes[0] == [block(dense)] + [block(moe)] * 4
    assert shapes[1] == {"embed": (12800, d), "final_norm": (d,),
                         "lm_head": (d, 12800)}

    cfg = dataclasses.replace(cfg, tie_embeddings=False, dtype="float32")
    bp, rest = pff_lm.init_state(cfg, 0)
    fan_in = {"wq": 0, "wkv_a": 0, "wkv_b": 0, "wo": (0, 1), "wi": -2,
              "wg": -2, "router": 0, "embed": None, "lm_head": 0}
    for k, unit in enumerate(bp):
        for path, a in jax.tree_util.tree_leaves_with_path(
                (unit, {n: rest[n] for n in ("final_norm", "lm_head")})):
            name = path[-1].key
            if a.ndim == 1:
                assert not np.asarray(a).any(), (k, path)
                continue
            if name == "wo":        # (H, v, d), (ff, d) or (E, ff, d)
                width = (a.shape[0] * a.shape[1] if "attn" in str(path)
                         else a.shape[-2])
            else:
                width = a.shape[fan_in[name]]
            assert np.std(a) * width ** 0.5 == pytest.approx(1, rel=0.1), \
                (k, path)
        moe = unit[0]["mlp"]
        if "router" in moe:
            for n in ("wi", "wg", "wo"):
                assert all(not np.allclose(moe[n][i], moe[n][j])
                           for i in range(len(moe[n]))
                           for j in range(i))
    assert not np.allclose(bp[1][0]["attn"]["wq"], bp[2][0]["attn"]["wq"])


def test_one_chapter_matches_reference(cfg):
    """One chapter of the sequential chapter trainer on the two-group
    stack against the reference from the same initial weights: every
    step's FF loss, the head's CE, and every trained parameter."""
    cfg = dataclasses.replace(cfg, tie_embeddings=False, dtype="float32")
    steps, batch, seq = 2, 2, 16
    source = data_lib.text_source(vocab=cfg.vocab, seq_len=seq, seed=0)
    data_iter = pff_lm.chapter_batches(source, batch=batch, steps=steps)
    params, _, losses, head_losses = pff_lm.train_chapters(
        cfg, data_iter, chapters=1, steps_per_chapter=steps, lr=1e-3,
        seed=0)
    init = transformer.init(jax.random.PRNGKey(0), cfg)
    bp, rest = pff_lm.split_params(cfg, init)
    nb = len(bp)
    toks = lambda k: [b["tokens"] for b in data_iter(0, k)]
    trained = list(bp)
    ref_losses, ref_head, _ = ref.chapters(
        ref.hparams(cfg), bp, {n: rest[n] for n in ("final_norm", "lm_head")},
        rest["embed"], [[toks(k) for k in range(nb)]], [toks(nb)], lr=1e-3,
        head_lr=1e-3, seed=0,
        on_block=lambda c, k, p: trained.__setitem__(k, p))
    np.testing.assert_allclose(np.stack(losses), ref_losses, rtol=1e-5)
    np.testing.assert_allclose(np.concatenate(head_losses), ref_head,
                               rtol=1e-5)
    # every block's change against the reference's, per output unit
    # (a column of a leaf): Adam turns a gradient that round-off alone
    # decides into a step of the full learning rate, so a few columns
    # differ by up to a percent (1e-7 loss gaps become 6e-3 there);
    # the median unit agrees to 1e-4
    got, _ = pff_lm.split_params(cfg, params)
    start, _ = pff_lm.split_params(cfg, init)
    for k in range(nb):
        ratios = []
        for a, b, c in zip(*(jax.tree.leaves(t[k])
                             for t in (got, trained, start))):
            cols = lambda u: np.asarray(u).reshape(-1, u.shape[-1])
            moved = np.linalg.norm(cols(b - c), axis=0)
            assert moved.max() > 0
            ratios.append(np.linalg.norm(cols(a - b), axis=0)[moved > 0]
                          / moved[moved > 0])
        assert np.median(np.concatenate(ratios)) < 1e-4, k
    for n in ("final_norm", "lm_head"):
        assert not np.array_equal(params[n], rest[n])


def test_benchmark_copy_of_the_reference_is_the_same_file():
    with open(ref.__file__) as a, open(os.path.join(
            ROOT, "bench", "ff_lm_reference.py")) as b:
        assert a.read() == b.read()


def test_traced_fit_names_the_lm_spans_and_counts_the_held_experts(cfg):
    """``api.fit`` on the two-group stack with a tracer: ``fit:init``,
    one ``task:train`` per block (its kind; MoE blocks also the
    assignments to held experts and their load spread), ``task:head``,
    ``fit:eval``, and the per-step FF losses in ``history``."""
    from repro import api
    source = data_lib.text_source(vocab=cfg.vocab, seq_len=16, seed=0)
    res = api.fit(cfg, source, backend="sequential", chapters=1,
                  steps_per_chapter=2, batch=2, seq=16, trace=True)
    spans = res.trace.spans
    names = [s.name for s in spans]
    for n in ("fit:init", "task:head", "fit:eval"):
        assert n in names
    train = [s.attrs for s in spans if s.name == "task:train"]
    assert [a["kind"] for a in train] == ["mla_dense", "mla_moe", "mla_moe"]
    assert "routed" not in train[0]
    tokens = 2 * 2 * 16 * 2                     # pos + neg, 2 steps
    for a in train[1:]:
        assert 0 < a["routed"] <= tokens * cfg.moe.top_k
        assert a["load_max_over_mean"] >= 1.0
    assert [n for n, _ in res.history] == list(range(1, 7))
    assert len(res.head_history) == 2
