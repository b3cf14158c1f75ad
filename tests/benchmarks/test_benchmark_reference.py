"""The plain references against the program's towers at a small size on the
CPU, both variants: forward, loss and gradients. The program computes in
float32 here, so that the comparison is of the mathematics and not of
bfloat16's rounding."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PRESET = {"bert": "bert_mini_v5p16", "t5": "mt5_multilingual"}
TINY = {"model.model_dim": 32, "model.mlp_dim": 64, "model.num_layers": 2,
        "model.num_heads": 2, "model.out_dim": 16, "model.dropout": 0.0,
        "model.dtype": "float32", "data.vocab_size": 97, "data.page_len": 24,
        "data.query_len": 8, "mesh.data": 1, "mesh.model": 1}
ARCH = {"layers": 2, "heads": 2}


@pytest.fixture(scope="module", params=["bert", "t5"])
def pair(request):
    import jax
    import jax.numpy as jnp
    from benchmarks import weights
    from dnn_page_vectors_tpu.config import get_config
    from dnn_page_vectors_tpu.models.factory import build_two_tower
    variant = request.param
    cfg = get_config(PRESET[variant], TINY)
    model = build_two_tower(cfg, cfg.data.vocab_size)
    rng = np.random.default_rng(3)
    q = rng.integers(1, 97, size=(6, 8)).astype(np.int32)
    p = rng.integers(1, 97, size=(6, 24)).astype(np.int32)
    q[0, 5:] = 0            # padding on both sides
    p[1, 10:] = 0
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.asarray(q), jnp.asarray(p))
    params = weights.make_params(tree, seed=2**31 + 9)
    return variant, model, params, jnp.asarray(q), jnp.asarray(p)


def test_forward_agrees(pair):
    import jax
    from benchmarks.reference import towers
    variant, model, params, q, p = pair
    with jax.default_matmul_precision("highest"):
        gq, gp, _, scale = model.apply(params, q, p)
    tw = params["params"]
    rq = towers.tower(tw["query_tower"], q, variant, **_lh())
    rp = towers.tower(tw["page_tower"], p, variant, **_lh())
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(rp), atol=2e-5)
    assert float(scale) == pytest.approx(20.0, rel=1e-6)


def _lh():
    return {"num_layers": ARCH["layers"], "num_heads": ARCH["heads"]}


def test_loss_and_gradients_agree(pair):
    import jax
    from benchmarks.reference import train_ref
    from dnn_page_vectors_tpu.models.losses import cosine_contrastive_loss
    variant, model, params, q, p = pair

    def loss_fn(prm):
        qv, pv, neg, scale = model.apply(prm, q, p)
        return cosine_contrastive_loss(qv, pv, scale, neg)[0]

    with jax.default_matmul_precision("highest"):
        want, wgrads = jax.value_and_grad(loss_fn)(params)
    ref = train_ref.TrainReference(
        dict(ARCH, variant=variant),
        {"learning_rate": 1e-3, "warmup_steps": 1, "decay_steps": 10,
         "end_factor": 0.1, "weight_decay": 0.0, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8, "clip_global_norm": 1.0}, block_rows=4)
    got, ggrads = ref.loss_and_grads(params, q, p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat_w = jax.tree_util.tree_leaves_with_path(wgrads)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(ggrads))
    assert len(flat_w) == len(flat_g)
    scale = max(float(np.abs(np.asarray(w)).max()) for _, w in flat_w)
    for path, w in flat_w:
        np.testing.assert_allclose(np.asarray(flat_g[path]), np.asarray(w),
                                   atol=2e-5 * scale, err_msg=str(path))


def test_reference_optimizer_follows_optax(pair):
    """Clip, AdamW and the warm-up schedule, three steps, against the
    program's own optimizer on the same gradients."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import train_ref
    from dnn_page_vectors_tpu.config import TrainConfig
    from dnn_page_vectors_tpu.train.optimizer import make_optimizer
    _, _, params, _, _ = pair
    opt = {"learning_rate": 5e-4, "warmup_steps": 100, "decay_steps": 1000,
           "end_factor": 0.1, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
           "eps": 1e-8, "clip_global_norm": 1.0}
    tx = make_optimizer(TrainConfig(learning_rate=5e-4, warmup_steps=100,
                                    steps=1000, weight_decay=0.01))
    ref = train_ref.TrainReference(dict(ARCH, variant="bert"), opt, 4)
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)
    p_ref, (mu, nu) = copy(params), ref.init_opt(params)
    p_opt, state = copy(params), tx.init(params)
    key = jax.random.PRNGKey(1)
    for i in range(3):
        grads = jax.tree_util.tree_map(
            lambda x: 3.0 * jax.random.normal(
                jax.random.fold_in(key, i), x.shape), params)
        upd, state = tx.update(grads, state, p_opt)
        p_opt = jax.tree_util.tree_map(jnp.add, p_opt, upd)
        p_ref, mu, nu, _ = ref.apply(p_ref, mu, nu, grads, i)
    moved = train_ref.leaf_norms(p_opt, minus=params)
    gap = train_ref.leaf_norms(p_opt, minus=p_ref)
    assert max(moved.values()) > 0
    for name in moved:
        assert gap[name] <= 1e-3 * max(moved[name], 1e-12) + 1e-9, name
    assert train_ref.learning_rate(opt, 0) == 0.0
    assert train_ref.learning_rate(opt, 100) == pytest.approx(5e-4)
    assert train_ref.learning_rate(opt, 1000) == pytest.approx(5e-5)


def test_plain_wordpiece_equals_the_programs_tokenizer(tmp_path):
    from benchmarks import corpus, vocab
    from dnn_page_vectors_tpu.data.subword import SubwordTokenizer
    path = corpus.write_synth_jsonl(str(tmp_path / "s.jsonl"), 300, seed=4)
    voc = vocab.build_vocab(path, 2000)
    assert len(voc) + vocab.RESERVED == 2000
    recs = corpus.read_records(path, range(300))
    texts = [recs[i]["page"] for i in range(300)] + ["", "xq 7 zz"]
    tok = SubwordTokenizer(voc, style="wordpiece", max_tokens=40)
    np.testing.assert_array_equal(tok.encode_batch(texts),
                                  vocab.encode(voc, texts, 40))
    same = corpus.write_synth_jsonl(str(tmp_path / "t.jsonl"), 300, seed=4)
    assert open(path).read() == open(same).read()


def test_synthetic_writer_is_the_programs_copy(tmp_path):
    from benchmarks import corpus
    from dnn_page_vectors_tpu.data.synth import write_synth_jsonl
    mine = corpus.write_synth_jsonl(str(tmp_path / "a.jsonl"), 500, seed=11)
    theirs = write_synth_jsonl(str(tmp_path / "b.jsonl"), 500, seed=11)
    assert open(mine).read() == open(theirs).read()
    ids = corpus.hash_ids(2**31 + 3, 1, [0, 5, 10**7], 128, 250112)
    assert ids.min() >= 1 and ids.max() < 250112 and ids.shape == (3, 128)
    assert (ids == corpus.hash_ids(2**31 + 3, 1, [0, 5, 10**7], 128,
                                   250112)).all()
