"""models/granite_hybrid.py (Granite-4.0-H as an embedding tower) at tiny
widths: the program against the benchmark's plain reference (vectors, loss,
every leaf's gradient, and a step through `Trainer`), the share of the
experts tied to the uncut layer, the router (a softmax over the selected
alone, nothing dropped), grouped-query causal attention at a stated scale,
right padding, and the weights `BulkEmbedder` holds in bfloat16."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import corpus, weights, weights_ssm  # noqa: E402
from benchmarks.reference import granitemoehybrid as ref  # noqa: E402
from dnn_page_vectors_tpu.config import get_config  # noqa: E402
from dnn_page_vectors_tpu.infer import bulk_embed  # noqa: E402
from dnn_page_vectors_tpu.models import glm_moe, granite_hybrid  # noqa: E402
from dnn_page_vectors_tpu.models.factory import build_two_tower  # noqa: E402
from dnn_page_vectors_tpu.models.losses import (  # noqa: E402
    cosine_contrastive_loss)
from dnn_page_vectors_tpu.train.loop import Trainer, moe_metrics  # noqa: E402

# hidden 64, 8 Mamba heads of 16 (expand 2), state 16, chunk 8, 4 + 2
# attention heads of 16, 8 experts of width 16 with 3 a token, pattern m m a m
TYPES = ("mamba", "mamba", "attention", "mamba")
ARCH = {"layer_types": list(TYPES), "num_attention_heads": 4,
        "num_key_value_heads": 2, "attention_multiplier": 1 / 16,
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 8, "num_experts_per_tok": 3,
        "rms_norm_eps": 1e-5, "experts_held_start": 2}
VOCAB = 100


def _config(dtype="float32", attention="flash", held=4, start=2, **more):
    ov = {"model.model_dim": 64, "model.mlp_dim": 16,
          "model.shared_intermediate_size": 32, "model.num_heads": 4,
          "model.num_key_value_heads": 2,
          "model.attention_multiplier": 1 / 16, "model.mamba_n_heads": 8,
          "model.mamba_d_head": 16, "model.mamba_d_state": 16,
          "model.mamba_chunk_size": 8, "model.n_routed_experts": 8,
          "model.num_experts_per_tok": 3, "model.num_layers": 4,
          "model.layer_types": TYPES, "model.experts_held": held,
          "model.experts_held_start": start, "model.out_dim": 32,
          "model.dtype": dtype, "model.weights_dtype": "float32",
          "model.attention": attention, "data.vocab_size": VOCAB,
          "data.page_len": 40, "data.query_len": 16,
          "serve.encode_batch": 2}
    ov.update(more)
    return get_config("granite4_h_small_ep2", ov)


def _ids(seed=0):
    rng = np.random.default_rng(seed)
    q = rng.integers(1, VOCAB, (4, 16))
    q[1, 9:] = 0                                   # padding at the end
    p = rng.integers(1, VOCAB, (4, 40))
    p[2, 20:] = 0
    return jnp.asarray(q, jnp.int32), jnp.asarray(p, jnp.int32)


def _model_and_params(cfg, seed=12345, weights_dtype="float32"):
    model = build_two_tower(cfg, VOCAB)
    q, p = _ids()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), q, p)
    return model, weights_ssm.make_params(
        tree, seed, weights_dtype=weights_dtype,
        float32_leaves=("/router/kernel", "/proj/kernel"))


def _program(model, params, q, p):
    (qv, pv, _, scale), st = model.apply(params, q, p,
                                         mutable=[glm_moe.STATS])
    return cosine_contrastive_loss(qv, pv, scale, None)[0], (qv, pv, st)


def _reference(params, q, p, arch=ARCH):
    t = params["params"]["query_tower"]
    qv, c1 = ref.tower(t, q, arch)
    pv, c2 = ref.tower(t, p, arch)
    loss = ref.towers.contrastive_loss(qv, pv, params["params"]["log_scale"])
    return loss, (qv, pv, c1 + c2)


# float32: rounding of another order of summation (the chunked scan against
# the recurrence, the flash tiles against a materialised softmax). bfloat16
# compute on bfloat16-held weights: 8 bits of mantissa through 4 blocks at
# width 64 move a vector by up to 1.6% and a leaf's gradient by up to 15% of
# its norm over seeds 1-12 (0.67% and 3.3% on this one); a token whose 3rd and
# 4th router logits lie within that error of each other picks another expert
# (1 to 9 of the 2,688 assignments over those seeds, 1 on this one: half a
# percent is allowed).
@pytest.mark.parametrize("dtype,attention,seed,tol,grad_tol", [
    ("float32", "flash", 12345, 1e-5, 3e-5),
    ("float32", "dense", 12345, 1e-5, 3e-5),
    ("bfloat16", "flash", 2, 0.02, 0.1)])
def test_tower_equals_the_plain_reference(dtype, attention, seed, tol,
                                          grad_tol):
    cfg = _config(dtype, attention)
    model, params = _model_and_params(cfg, seed, weights_dtype=dtype)
    q, p = _ids()
    (l1, (q1, p1, st)), g1 = jax.jit(jax.value_and_grad(
        lambda v: _program(model, v, q, p), has_aux=True))(params)
    (l2, (q2, p2, counts)), g2 = jax.jit(jax.value_and_grad(
        lambda v: _reference(v, q, p), has_aux=True))(params)
    assert abs(float(l1) - float(l2)) <= tol * abs(float(l2))
    for a, b in ((q1, q2), (p1, p2)):
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max())
    norm = lambda t: float(jnp.sqrt(jnp.sum(jnp.square(
        t.astype(jnp.float32)))))
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    for (path, a), b in zip(flat1, jax.tree_util.tree_leaves(g2)):
        assert norm(a.astype(jnp.float32) - b.astype(jnp.float32)) \
            <= grad_tol * max(norm(b), 1e-3), weights.path_str(path)
    m = moe_metrics(st[glm_moe.STATS])
    assert int(m["moe/dropped"]) == 0
    assert m["moe/assignments_held"].shape == (4, 4)
    tokens = q.size + p.size
    flipped = int(jnp.abs(m["moe/assignments_held"] - counts).sum())
    assert flipped <= (0 if dtype == "float32" else 0.005 * 3 * tokens * 4)
    np.testing.assert_array_equal(
        m["moe/assignments_held"].sum(1) + m["moe/assignments_absent"],
        [3 * tokens] * 4)


def test_trainer_steps_the_tower(tmp_path):
    """`Trainer.compiled_step` on the tower: the first step's loss is the
    reference's on the same rows, nothing is dropped, and AdamW moves the
    state-space leaves too (by the second step: the warm-up starts at 0)."""
    cfg = _config(**{"train.batch_size": 4, "train.warmup_steps": 1,
                     "mesh.data": 1})
    seed = 7
    toks = tuple(corpus.HashTokenizer(VOCAB, n, seed, side)
                 for side, n in enumerate((16, 40)))
    trainer = Trainer(cfg, corpus=corpus.IdCorpus(64), tokenizers=toks,
                      workdir=str(tmp_path))
    state = trainer.init_state()
    before = jax.tree_util.tree_map(np.asarray, state.params)
    batch = next(trainer.batches(start_step=0))
    rows = np.asarray(batch["page_id"])
    step = trainer.compiled_step(state)
    new, metrics = step(state, batch, trainer.base_rng())
    q, p = (jnp.asarray(corpus.hash_ids(seed, side, rows, n, VOCAB))
            for side, n in enumerate((16, 40)))
    want, _ = _reference(before, q, p)
    assert float(metrics["loss"]) == pytest.approx(float(want), rel=1e-5)
    assert float(metrics["moe/dropped"]) == 0.0
    new, _ = step(new, batch, trainer.base_rng())   # warm-up: step 0 moves 0
    mixer = lambda t: t["params"]["query_tower"]["block0"]["mixer"]
    for leaf in ("A_log", "dt_bias", "D", "conv_kernel"):
        assert not np.array_equal(np.asarray(mixer(new.params)[leaf]),
                                  mixer(before)[leaf]), leaf


# -- the expert layer ---------------------------------------------------------

def _layer(held, start, dtype=jnp.float32):
    return glm_moe.RoutedExperts(
        64, 16, 8, 3, 1.0, held, start, dtype=dtype, router="softmax_topk",
        shared_dim=32)


def _layer_params(seed=5):
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"w_gate": s(8, 64, 16), "w_up": s(8, 64, 16),
            "w_down": s(8, 16, 64), "router": {"kernel": s(64, 8)},
            "shared": {"wi_0": {"kernel": s(64, 32)},
                       "wi_1": {"kernel": s(64, 32)},
                       "wo_mlp": {"kernel": s(32, 64)}}}
    return weights_ssm.make_params(tree, seed)


def _share(p, start, held):
    cut = lambda w: w[start:start + held]
    return dict(p, w_gate=cut(p["w_gate"]), w_up=cut(p["w_up"]),
                w_down=cut(p["w_down"]))


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 2 shares of 4 experts, plus the shared
    expert once, are the uncut reference's layer."""
    p = _layer_params()
    u = jax.random.normal(jax.random.key(1), (2, 24, 64))
    whole, counts = ref.experts(p, u.reshape(48, 64),
                                dict(ARCH, experts_held_start=0))
    shared = ref._swiglu(p["shared"], u.reshape(48, 64), ref.identity)
    parts, held = [], []
    for start in (0, 4):
        out, st = _layer(4, start).apply({"params": _share(p, start, 4)}, u)
        parts.append(out.reshape(48, 64) - shared)
        held.append(st["held"])
        assert int(st["dropped"]) == 0
        assert int(st["held"].sum() + st["absent"]) == 48 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(held), counts)


@pytest.mark.parametrize("shares", [[(0, 8)], [(0, 4), (4, 4)]],
                         ids=["all_held", "two_shares"])
def test_router_weights_are_a_softmax_over_the_selected_alone(shares):
    """With every expert the same function E, Routed(u) = E(u) x the sum of
    the weights: 1 over held + absent. (A softmax over all 8 logits would
    leave the selected 3 with less than 1.) Nothing is dropped, and where
    all are held nothing is absent."""
    p = _layer_params(seed=6)
    same = lambda w: jnp.broadcast_to(w[:1], w.shape)
    p = dict(p, w_gate=same(p["w_gate"]), w_up=same(p["w_up"]),
             w_down=same(p["w_down"]))
    u = jax.random.normal(jax.random.key(2), (2, 24, 64))
    flat = u.reshape(48, 64)
    one = (jax.nn.silu(flat @ p["w_gate"][0]) * (flat @ p["w_up"][0])) \
        @ p["w_down"][0]
    shared = ref._swiglu(p["shared"], flat, ref.identity)
    total = 0
    for start, held in shares:
        out, st = _layer(held, start).apply(
            {"params": _share(p, start, held)}, u)
        total = total + out.reshape(48, 64) - shared
        assert int(st["dropped"]) == 0
        assert int(st["absent"]) == (0 if held == 8 else
                                     48 * 3 - int(st["held"].sum()))
    np.testing.assert_allclose(total, one, rtol=1e-4, atol=1e-5)
    chosen, weight = ref.route(p, flat, ARCH)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)
    _, wrong = ref.route(p, flat, ARCH, softmax_all=True)
    assert float(wrong.sum(-1).max()) < 0.99


# -- attention, padding ---------------------------------------------------------

@pytest.mark.parametrize("kind", ["flash", "dense"])
def test_grouped_query_attention_at_the_stated_scale(kind):
    sizes = build_two_tower(_config(), VOCAB).query_tower.sizes
    attn = granite_hybrid.GqaAttention(sizes, dtype=jnp.float32, kind=kind)
    u = jax.random.normal(jax.random.key(3), (2, 40, 64))
    mask = jnp.arange(40)[None, :] < jnp.asarray([[40], [23]])
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    p = weights_ssm.make_params(
        {"wq": {"kernel": s(64, 64)}, "wk": {"kernel": s(64, 32)},
         "wv": {"kernel": s(64, 32)}, "wo": {"kernel": s(64, 64)}}, 9)
    got = attn.apply({"params": p}, u, mask)
    want = ref.attention(p, u, mask, ARCH)
    keep = mask[..., None]
    np.testing.assert_allclose(jnp.where(keep, got, 0),
                               jnp.where(keep, want, 0), rtol=1e-4,
                               atol=1e-5)
    # the scale is 1/16 here, not 1/sqrt(16): the other one reads otherwise
    other = ref.attention(p, u, mask, dict(ARCH, attention_multiplier=0.25))
    assert float(jnp.abs(jnp.where(keep, other - want, 0)).max()) > 1e-2


def test_right_padding_cannot_reach_the_pooled_vector():
    cfg = _config()
    model, params = _model_and_params(cfg)
    q, _ = _ids()
    enc = jax.jit(lambda ids: model.apply(params, ids,
                                          method="encode_query"))
    padded = enc(q)[1]                     # row 1: 9 tokens, then 7 pads
    alone = enc(q[1:2, :9])[0]
    np.testing.assert_allclose(padded, alone, rtol=1e-5, atol=1e-6)
    other = q.at[1, 9:].set(7)             # tokens where the pads were
    assert float(jnp.abs(enc(other)[1] - alone).max()) > 1e-3


# -- the weights inference holds -------------------------------------------------

def _embedder(cfg, params, model):
    from dnn_page_vectors_tpu.parallel.mesh import make_mesh
    tok = corpus.HashTokenizer(VOCAB, 16, 1, 0)
    return bulk_embed.BulkEmbedder(cfg, model, params, tok,
                                   make_mesh(cfg.mesh), query_tok=tok)


@pytest.mark.parametrize("arrives", ["float32", "bfloat16"])
def test_bulk_embedder_holds_the_matrices_in_bfloat16(arrives):
    """One cast or none: a tree that arrives in bfloat16 stays the arrays
    it is; what the tower computes with in float32 stays float32; and the
    lowered encode holds no float32 array the size of a held matrix."""
    cfg = _config("bfloat16", **{"model.weights_dtype": "bfloat16",
                                 "mesh.data": 1,
                                 "model.out_dim": 24})   # no matrix's side
    model, params = _model_and_params(cfg, weights_dtype=arrives)
    emb = _embedder(cfg, params, model)
    assert emb.counts_encode
    flat = jax.tree_util.tree_flatten_with_path(emb.params)[0]
    lowered = set()
    for path, leaf in flat:
        name = weights.path_str(path)
        kept = leaf.ndim < 2 or "router" in name or "/proj/" in name
        assert leaf.dtype == (jnp.float32 if kept else jnp.bfloat16), name
        if not kept:
            lowered.add("x".join(map(str, leaf.shape)))
    if arrives == "bfloat16":
        held = bulk_embed.hold_weights(params, "bfloat16")
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(params),
                                          jax.tree_util.tree_leaves(held)))
    ids = jnp.zeros((2, 24), jnp.int32)      # 48 tokens: no matrix's side
    text = emb._encode_query.lower(emb.params, ids).as_text()
    f32 = set(re.findall(r"tensor<([0-9x]+)xf32>", text))
    assert not (f32 & lowered), f32 & lowered
    vecs = emb.embed_queries(np.asarray(_ids()[0][:2]))
    assert vecs.shape == (2, 24) and np.isfinite(vecs).all()
    # the tower says that it counts, and one call hands back the four sums
    # and the per-expert counts they were made from
    dev, (sums, held) = emb.encode_query_call(np.asarray(_ids()[0][:2]))
    np.testing.assert_array_equal(np.asarray(dev), vecs)
    assert held.shape == (len(cfg.model.layer_types), cfg.model.experts_held)
    assert int(sums[1]) == int(held.sum()) and int(sums[3]) == 0


def test_the_recomputations_names_are_inert_in_a_tower_that_recomputes_nothing(
        monkeypatch):
    """The shared expert's `SwiGlu` and the causal flash forward name values
    for the sparse tower's half-block recomputation (models/glm_moe.py:
    `_KEPT`). This tower recomputes nothing: its counted encode lowers to
    the text it has with the names taken off, which holds none of them, and
    a step's loss and gradients are the same bits."""
    from dnn_page_vectors_tpu.ops import flash_attention
    cfg = _config("bfloat16", **{"model.weights_dtype": "bfloat16",
                                 "mesh.data": 1})
    model, params = _model_and_params(cfg, weights_dtype="bfloat16")
    q, p = _ids()
    ids = jnp.zeros((2, 16), jnp.int32)

    def lower_and_step():
        emb = _embedder(cfg, params, model)
        assert emb.counts_encode
        text = emb._encode_query.lower(emb.params, ids).as_text()
        # but for the numbers the lowering gives its private functions
        return (re.sub(r"@(\w+?)_\d+\b", r"@\1", text),
                jax.jit(jax.value_and_grad(
                    lambda v: _program(model, v, q, p)[0]))(params))

    text, (loss, grads) = lower_and_step()
    for module in (glm_moe, flash_attention):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    text0, (loss0, grads0) = lower_and_step()
    assert text == text0
    assert not [name for name in ("shared_gate", "shared_up")
                + flash_attention.CAUSAL_RESIDUALS if name in text]
    assert float(loss) == float(loss0)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=weights.path_str(path))


def test_a_float32_tower_stays_float32_bit_for_bit():
    """`bert_mini`'s preset: `hold_weights` hands back the tree it was
    given, and the encode hands back vectors alone."""
    cfg = get_config("bert_mini_v5p16", {
        "model.model_dim": 32, "model.mlp_dim": 64, "model.num_layers": 1,
        "model.num_heads": 2, "model.out_dim": 16, "data.vocab_size": VOCAB,
        "data.page_len": 16, "data.query_len": 8, "mesh.data": 1})
    assert cfg.model.weights_dtype == "float32"
    model = build_two_tower(cfg, VOCAB)
    ids = jnp.ones((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids)
    assert bulk_embed.hold_weights(params, "float32") is params
    emb = _embedder(cfg, params, model)
    assert not emb.counts_encode
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(emb.params)):
        assert b.dtype == a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert isinstance(emb._encode_query(emb.params, emb._put(
        np.ones((8, 8), np.int32))), jax.Array)
    vecs, counts = emb.encode_query_call(np.ones((8, 8), np.int32))
    assert isinstance(vecs, jax.Array) and counts is None
