"""models/falcon_h1.py (Falcon-H1 as an embedding tower) at tiny widths, all
twelve multipliers away from 1: the program against the benchmark's plain
reference (vectors, loss, every leaf's gradient, and a step through
`Trainer`), the scan with groups, each multiplier and the order of the
projection's segments, the grouped gated norm, grouped-query attention with
rotary, right padding, and what `BulkEmbedder` holds and counts for a tower
without routed layers."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import corpus, tiny_h1, weights, weights_h1  # noqa: E402
from benchmarks.reference import falcon_h1 as ref  # noqa: E402
from dnn_page_vectors_tpu.config import get_config  # noqa: E402
from dnn_page_vectors_tpu.infer import bulk_embed  # noqa: E402
from dnn_page_vectors_tpu.models import granite_hybrid  # noqa: E402
from dnn_page_vectors_tpu.models.factory import build_two_tower  # noqa: E402
from dnn_page_vectors_tpu.models.losses import (  # noqa: E402
    cosine_contrastive_loss)
from dnn_page_vectors_tpu.models.transformer import RmsNorm  # noqa: E402
from dnn_page_vectors_tpu.ops import ssd_scan as scan_ops  # noqa: E402
from dnn_page_vectors_tpu.train.loop import Trainer  # noqa: E402

# hidden 64, 4 mixer heads of 8 in 2 groups, state 16, chunk 8, 4 + 2
# attention heads of 16, FFN 96, 3 layers (benchmarks/tiny_h1.py)
ARCH = dict(tiny_h1.PUBLISHED, rms_norm_eps=1e-5)
GAINS = tiny_h1.ASSUMED["gains"]
VOCAB = 100
MULTIPLIERS = ["embedding_multiplier", "ssm_in_multiplier",
               "ssm_out_multiplier", "attention_in_multiplier",
               "key_multiplier", "attention_out_multiplier"] \
    + [f"ssm_multipliers.{i}" for i in range(5)] \
    + [f"mlp_multipliers.{i}" for i in range(2)]


def _config(dtype="float32", attention="flash", **more):
    ov = {"model." + field: ARCH[key]
          for key, field in tiny_h1._OVERRIDES.items()}
    ov.update({"model.num_layers": 3, "model.out_dim": 32,
               "model.dtype": dtype, "model.weights_dtype": "float32",
               "model.attention": attention, "data.vocab_size": VOCAB,
               "data.page_len": 40, "data.query_len": 16,
               "serve.encode_batch": 2})
    ov.update(more)
    return get_config("falcon_h1_34b_pp12", ov)


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
    return model, weights_h1.make_params(
        tree, seed, weights_dtype=weights_dtype,
        float32_leaves=("/proj/kernel",), gains=GAINS,
        segments={"in_proj": ref.segment_widths(ARCH)})


def _program(model, params, q, p):
    qv, pv, _, scale = model.apply(params, q, p)
    return cosine_contrastive_loss(qv, pv, scale, None)[0], (qv, pv)


def _reference(params, q, p, arch=ARCH, **how):
    t = params["params"]["query_tower"]
    qv, pv = ref.tower(t, q, arch, **how), ref.tower(t, p, arch, **how)
    return ref.towers.contrastive_loss(qv, pv, params["params"]["log_scale"]), \
        (qv, pv)


def _norm(t):
    return float(jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)))))


# float32: rounding of another order of summation (the chunked scan against
# the recurrence, the flash tiles against a materialised softmax). bfloat16
# compute on bfloat16-held weights: 8 bits of mantissa through 3 blocks at
# width 64, under a softmax whose scores spread by about 2 (a rounded score
# moves a weight by its own rounding), move the loss by up to 2.7%, a vector
# by up to 2.6% and a leaf's gradient by up to 25% of its norm over seeds 1-8
# (0.9%, 2.1% and 17% on this one; a leaf's norm is held to at least a
# thousandth of the largest leaf's).
@pytest.mark.parametrize("dtype,attention,seed,tol,grad_tol", [
    ("float32", "flash", 12345, 1e-5, 3e-5),
    ("float32", "dense", 12345, 1e-5, 3e-5),
    ("bfloat16", "flash", 2, 0.04, 0.2)])
def test_tower_equals_the_plain_reference(dtype, attention, seed, tol,
                                          grad_tol):
    cfg = _config(dtype, attention)
    model, params = _model_and_params(cfg, seed, weights_dtype=dtype)
    q, p = _ids()
    (l1, (q1, p1)), g1 = jax.jit(jax.value_and_grad(
        lambda v: _program(model, v, q, p), has_aux=True))(params)
    (l2, (q2, p2)), g2 = jax.jit(jax.value_and_grad(
        lambda v: _reference(v, q, p), has_aux=True))(params)
    assert abs(float(l1) - float(l2)) <= tol * abs(float(l2))
    for a, b in ((q1, q2), (p1, p2)):
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max())
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    biggest = max(_norm(b) for b in jax.tree_util.tree_leaves(g2))
    for (path, a), b in zip(flat1, jax.tree_util.tree_leaves(g2)):
        assert _norm(a.astype(jnp.float32) - b.astype(jnp.float32)) \
            <= grad_tol * max(_norm(b), 1e-3 * biggest), \
            weights.path_str(path)


def test_trainer_steps_the_tower(tmp_path):
    """`Trainer.compiled_step` on a tower that sows no `moe_stats`: the first
    step's loss is the reference's on the same rows, and AdamW moves the
    state-space leaves too (by the second step: the warm-up starts at 0)."""
    cfg = _config(**{"train.batch_size": 4, "train.warmup_steps": 1,
                     "mesh.data": 1})
    seed = 7
    toks = tuple(corpus.HashTokenizer(VOCAB, n, seed, side)
                 for side, n in enumerate((16, 40)))
    trainer = Trainer(cfg, corpus=corpus.IdCorpus(64), tokenizers=toks,
                      workdir=str(tmp_path))
    assert not trainer._moe
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
    assert not [k for k in metrics if k.startswith("moe/")]
    new, _ = step(new, batch, trainer.base_rng())   # warm-up: step 0 moves 0
    mixer = lambda t: t["params"]["query_tower"]["block0"]["mixer"]
    for leaf in ("A_log", "dt_bias", "D", "conv_kernel"):
        assert not np.array_equal(np.asarray(mixer(new.params)[leaf]),
                                  mixer(before)[leaf]), leaf


# -- the scan with groups -----------------------------------------------------

CHUNK = 8


def _scan_inputs(L, G, B=2, H=4, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (B, L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, L, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, L, G, N)),
            jax.random.normal(k[4], (B, L, G, N)))


@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("L", [5, 8, 20])
def test_grouped_scan_equals_the_grouped_recurrence(L, G):
    """Below, at and across chunk boundaries, forward and gradients; head h
    reads group h // (H / G): the recurrence group by group, written out."""
    x, d, a, b, c = args = _scan_inputs(L, G)
    want = scan_ops.ssd_recurrence(*args)
    r = 4 // G
    by_hand = jnp.concatenate([
        scan_ops.ssd_recurrence(x[:, :, g * r:(g + 1) * r],
                                d[:, :, g * r:(g + 1) * r],
                                a[g * r:(g + 1) * r], b[:, :, g], c[:, :, g])
        for g in range(G)], axis=2)
    np.testing.assert_allclose(want, by_hand, rtol=1e-6, atol=1e-6)
    got = scan_ops.ssd_scan(*args, CHUNK)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    grad = lambda f: jax.grad(lambda *t: jnp.sum(jnp.square(f(*t))),
                              argnums=(0, 1, 2, 3, 4))
    for g, w in zip(grad(lambda *t: scan_ops.ssd_scan(*t, CHUNK))(*args),
                    grad(scan_ops.ssd_recurrence)(*args)):
        assert float(jnp.abs(g - w).max()) <= 2e-5 * float(jnp.abs(w).max())
    if G > 1:       # the groups are not one another's
        same = scan_ops.ssd_scan(x, d, a, jnp.broadcast_to(
            b[:, :, :1], b.shape), jnp.broadcast_to(c[:, :, :1], c.shape),
            CHUNK)
        assert float(jnp.abs(same - want).max()) > 1e-2 * scale


def test_one_group_is_the_scan_as_it_was_bit_for_bit():
    """[B, L, 1, N] and [B, L, N] give the same bits, and the same jaxpr as
    the one-group body alone: nothing is added for a tower with one group."""
    x, d, a, b, c = _scan_inputs(20, 1, seed=3)
    flat = scan_ops.ssd_scan(x, d, a, b[:, :, 0], c[:, :, 0], CHUNK)
    np.testing.assert_array_equal(scan_ops.ssd_scan(x, d, a, b, c, CHUNK),
                                  flat)
    body = lambda *t: scan_ops._scan_group(*t, CHUNK, True)
    args = (x, d, a, b[:, :, 0], c[:, :, 0])
    assert str(jax.make_jaxpr(body)(*args)) == str(jax.make_jaxpr(
        lambda *t: scan_ops.ssd_scan(*t, CHUNK))(*args))


# -- the multipliers ----------------------------------------------------------

def _without(name):
    """ARCH with one multiplier set to 1, and the config overrides to it."""
    key, _, i = name.partition(".")
    if i:
        value = list(ARCH[key])
        value[int(i)] = 1.0
    else:
        value = 1.0
    return dict(ARCH, **{key: value}), {"model." + key: value}


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moves_the_vector(name):
    """Set to 1, the program's vector changes by far more than float32's
    tolerance, and still equals the reference's under the same sizes: no
    multiplier is dropped, none is applied twice."""
    arch, ov = _without(name)
    q, _ = _ids()
    model, params = _model_and_params(_config())
    enc = lambda m: jax.jit(lambda v: m.apply(v, q, method="encode_query"))(
        params)
    base = enc(model)
    other = enc(build_two_tower(_config(**ov), VOCAB))
    scale = float(jnp.abs(base).max())
    assert float(jnp.abs(other - base).max()) > 1e-3 * scale
    want = ref.tower(params["params"]["query_tower"], q, arch)
    assert float(jnp.abs(other - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_segment_multipliers_land_on_z_x_b_c_dt_in_that_order():
    widths = ref.segment_widths(ARCH)
    assert widths == (32, 32, 32, 32, 4)
    mup = granite_hybrid.segment_multipliers(ARCH["ssm_multipliers"], widths)
    assert mup.shape == (132,)
    edges = np.cumsum((0,) + widths)
    for m, lo, hi in zip(ARCH["ssm_multipliers"], edges, edges[1:]):
        assert (mup[lo:hi] == np.float32(m)).all()
    with pytest.raises(ValueError):
        granite_hybrid.segment_multipliers((1.0, 2.0), widths)
    # the mixer with the B and C multipliers exchanged is another mixer
    swapped = list(ARCH["ssm_multipliers"])
    swapped[2], swapped[3] = swapped[3], swapped[2]
    q, _ = _ids()
    model, params = _model_and_params(_config())
    other = build_two_tower(_config(**{"model.ssm_multipliers": swapped}),
                            VOCAB)
    enc = lambda m: m.apply(params, q, method="encode_query")
    assert float(jnp.abs(enc(other) - enc(model)).max()) > 1e-3


# -- the grouped gated norm ---------------------------------------------------

def test_grouped_norm_is_by_group_and_not_the_ungrouped():
    x = jax.random.normal(jax.random.key(4), (2, 5, 32)) \
        * jnp.concatenate([jnp.full((16,), 3.0), jnp.full((16,), 0.5)])
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(5), (32,))
    got = RmsNorm(dtype=jnp.float32, eps=1e-5, groups=2).apply(
        {"params": {"scale": scale}}, x)
    halves = [x[..., :16], x[..., 16:]]
    want = jnp.concatenate([h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True)
                                         + 1e-5) for h in halves], -1) * scale
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    whole = RmsNorm(dtype=jnp.float32, eps=1e-5).apply(
        {"params": {"scale": scale}}, x)
    assert float(jnp.abs(whole - got).max()) > 0.1
    # the reference's mixer with the ungrouped norm is another mixer
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    p = weights_h1.make_params(
        {"A_log": s(4), "D": s(4), "conv_bias": s(96),
         "conv_kernel": s(4, 96), "dt_bias": s(4),
         "in_proj": {"kernel": s(64, 132)}, "norm": {"scale": s(32)},
         "out_proj": {"kernel": s(32, 64)}}, 3, gains=GAINS,
        segments={"in_proj": ref.segment_widths(ARCH)})
    u = jax.random.normal(jax.random.key(6), (2, 20, 64))
    by_group = ref.mixer(p, u, ARCH)
    assert float(jnp.abs(ref.mixer(p, u, ARCH, grouped_norm=False)
                         - by_group).max()) > 1e-2 * float(
        jnp.abs(by_group).max())
    sizes = build_two_tower(_config(), VOCAB).query_tower.sizes
    mine = granite_hybrid.Mamba2Mixer(sizes, dtype=jnp.float32).apply(
        {"params": p}, u)
    np.testing.assert_allclose(mine, by_group, rtol=2e-4, atol=2e-5)


# -- attention, padding -------------------------------------------------------

@pytest.mark.parametrize("theta", [1e11, 1e4])
@pytest.mark.parametrize("kind", ["flash", "dense"])
def test_grouped_query_attention_with_rotary(kind, theta):
    sizes = dataclasses.replace(
        build_two_tower(_config(), VOCAB).query_tower.sizes,
        rope_theta=theta)
    arch = dict(ARCH, rope_theta=theta)
    attn = granite_hybrid.GqaAttention(sizes, dtype=jnp.float32, kind=kind)
    u = jax.random.normal(jax.random.key(3), (2, 40, 64))
    mask = jnp.arange(40)[None, :] < jnp.asarray([[40], [23]])
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    p = weights_h1.make_params(
        {"wq": {"kernel": s(64, 64)}, "wk": {"kernel": s(64, 32)},
         "wv": {"kernel": s(64, 32)}, "wo": {"kernel": s(64, 64)}}, 9,
        gains=GAINS)
    got = attn.apply({"params": p}, u, mask)
    want = ref.attention(p, u, mask, arch)
    keep = mask[..., None]
    np.testing.assert_allclose(jnp.where(keep, got, 0),
                               jnp.where(keep, want, 0), rtol=1e-4,
                               atol=1e-5)
    # without the rotary, without the key multiplier, and under the other
    # theta it reads otherwise
    for how in ({"rotary": False}, {"key_multiplier": False}):
        other = ref.attention(p, u, mask, arch, **how)
        assert float(jnp.abs(jnp.where(keep, other - want, 0)).max()) > 1e-2
    other = ref.attention(p, u, mask, dict(arch, rope_theta=1e15 / theta))
    assert float(jnp.abs(jnp.where(keep, other - want, 0)).max()) > 1e-3


def test_right_padding_cannot_reach_the_pooled_vector():
    cfg = _config()
    model, params = _model_and_params(cfg)
    q, _ = _ids()
    enc = jax.jit(lambda ids: model.apply(params, ids,
                                          method="encode_query"))
    padded = enc(q)[1]                     # row 1: 9 tokens, then 7 pads
    alone = enc(q[1:2, :9])[0]
    # float32 under other shapes' order of summation: 1e-5 of the largest
    np.testing.assert_allclose(padded, alone, rtol=1e-5, atol=2e-5)
    other = q.at[1, 9:].set(7)             # tokens where the pads were
    assert float(jnp.abs(enc(other)[1] - alone).max()) > 1e-3


# -- what inference holds and counts ------------------------------------------

@pytest.mark.parametrize("arrives", ["float32", "bfloat16"])
def test_bulk_embedder_holds_bfloat16_and_counts_the_tokens(arrives):
    """The held tree is bfloat16 but for the leaves the configuration names
    (every vector, and `proj`), with one cast or none; and a tower without
    routed layers hands `encode.tokens` back through `encode_query_call`:
    the tokens counted, the routed layers' three at 0, no per-expert
    counts."""
    from dnn_page_vectors_tpu.parallel.mesh import make_mesh
    cfg = _config("bfloat16", **{"model.weights_dtype": "bfloat16",
                                 "mesh.data": 1})
    model, params = _model_and_params(cfg, weights_dtype=arrives)
    tok = corpus.HashTokenizer(VOCAB, 16, 1, 0)
    emb = bulk_embed.BulkEmbedder(cfg, model, params, tok,
                                  make_mesh(cfg.mesh), query_tok=tok)
    assert emb.counts_encode
    for path, leaf in jax.tree_util.tree_flatten_with_path(emb.params)[0]:
        name = weights.path_str(path)
        kept = leaf.ndim < 2 or "/proj/" in name
        assert leaf.dtype == (jnp.float32 if kept else jnp.bfloat16), name
    if arrives == "bfloat16":
        held = bulk_embed.hold_weights(params, "bfloat16")
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(params),
                                          jax.tree_util.tree_leaves(held)))
    ids = np.asarray(_ids()[0][:2])        # row 1: 9 tokens, then 7 pads
    dev, (sums, held) = emb.encode_query_call(ids)
    assert held is None
    assert np.asarray(sums).tolist() == [16 + 9, 0, 0, 0]
    vecs = emb.embed_queries(ids)
    np.testing.assert_array_equal(np.asarray(dev), vecs)
    assert vecs.shape == (2, 32) and np.isfinite(vecs).all()
