"""models/qwen3_next.py (Qwen3-Next as an embedding tower) at tiny widths:
the program against the benchmark's plain reference (vectors, loss, every
leaf's gradient, the routing), the layer pattern, the published layout of
the two input projections, the renormalised top-k router, the share of the
experts tied to the uncut layer, the zero-centred norm, a step through
`Trainer` with the linear attention's counters, and the preset against the
configuration file."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import corpus, harness, weights  # noqa: E402
from benchmarks import weights_qwen3_next as wq  # noqa: E402
from benchmarks.reference import qwen3_next as ref  # noqa: E402
from dnn_page_vectors_tpu.config import get_config  # noqa: E402
from dnn_page_vectors_tpu.models import glm_moe, qwen3_next  # noqa: E402
from dnn_page_vectors_tpu.models.factory import build_two_tower  # noqa: E402
from dnn_page_vectors_tpu.models.losses import (  # noqa: E402
    cosine_contrastive_loss)
from dnn_page_vectors_tpu.models.transformer import RmsNorm  # noqa: E402
from dnn_page_vectors_tpu.train.loop import (Trainer, gdn_metrics,  # noqa
                                             moe_metrics)

# hidden 64; 4 + 2 attention heads of 16, rotary on 4; linear attention 2
# key heads of 16 and 4 value heads of 8, conv 4, chunk 64; 8 experts of
# width 16 (4 held from the third), 3 a token, shared 16; layers g g g a
ARCH = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "partial_rotary_factor": 0.25, "rope_theta": 1e7,
        "rms_norm_eps": 1e-6, "full_attention_interval": 4,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 8,
        "num_experts_per_tok": 3, "num_hidden_layers": 4,
        "experts_held_start": 2, "chunk": 64}
VOCAB = 100
CELL = "qwen3_next_80b_ep16.train"


def _config(dtype="float32", attention="flash", held=4, start=2, **more):
    ov = {"model.model_dim": 64, "model.num_heads": 4,
          "model.num_key_value_heads": 2, "model.head_dim": 16,
          "model.linear_num_key_heads": 2, "model.linear_num_value_heads": 4,
          "model.linear_key_head_dim": 16, "model.linear_value_head_dim": 8,
          "model.moe_intermediate_size": 16,
          "model.shared_intermediate_size": 16, "model.n_routed_experts": 8,
          "model.num_experts_per_tok": 3, "model.experts_held": held,
          "model.experts_held_start": start, "model.num_layers": 4,
          "model.out_dim": 32,
          "model.dtype": dtype, "model.attention": attention,
          "data.vocab_size": VOCAB, "data.page_len": 136,
          "data.query_len": 16}
    ov.update(more)
    return get_config("qwen3_next_80b_ep16", ov)


def _ids(seed=0):
    rng = np.random.default_rng(seed)
    q = rng.integers(1, VOCAB, (4, 16))
    q[1, 9:] = 0                                   # padding at the end
    p = rng.integers(1, VOCAB, (4, 136))
    p[2, 70:] = 0
    return jnp.asarray(q, jnp.int32), jnp.asarray(p, jnp.int32)


def _model_and_params(cfg, seed=12345):
    model = build_two_tower(cfg, VOCAB)
    q, p = _ids()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), q, p)
    params = wq.make_params(tree, seed)
    # move the norms off their initial values, so that every one is read
    return model, jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jnp.sin(jnp.arange(x.size).reshape(x.shape))
        if weights.path_str(path).endswith(("centred_scale", "norm/scale"))
        else x, params)


def _program(model, params, q, p):
    (qv, pv, _, scale), st = model.apply(
        params, q, p, mutable=[glm_moe.STATS, qwen3_next.GDN_STATS])
    return cosine_contrastive_loss(qv, pv, scale, None)[0], (qv, pv, st)


def _reference(params, q, p, arch=ARCH):
    t = params["params"]["query_tower"]
    qv, c1 = ref.tower(t, q, arch)
    pv, c2 = ref.tower(t, p, arch)
    loss = ref.towers.contrastive_loss(qv, pv, params["params"]["log_scale"])
    return loss, (qv, pv, c1 + c2)


# float32: rounding of another order of summation (the chunked rule against
# the recurrence, the flash tiles against a materialised softmax). No
# bfloat16 case: at width 64 a bfloat16 tower flips 23 to 36 of the 2,688
# assignments over seeds 1-4 (a flipped expert at a pooled token turns its
# vector by up to 85 degrees) and moves the median leaf's gradient by 35-80%
# of its norm with routing or without; its agreement is judged at the
# published widths on the chip, by the cell's limits (PERF.md section 6).
@pytest.mark.parametrize("attention,remat", [("flash", True),
                                             ("dense", False)])
def test_tower_equals_the_plain_reference(attention, remat):
    tol, grad_tol = 2e-5, 1e-4
    cfg = _config("float32", attention, **{"model.remat_blocks": remat})
    model, params = _model_and_params(cfg)
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
    np.testing.assert_array_equal(m["moe/assignments_held"], counts)
    g = gdn_metrics(st[qwen3_next.GDN_STATS])
    np.testing.assert_array_equal(g["gdn/tokens"], [tokens] * 3)
    assert g["gdn/state_norm_max"].shape == (3,)


def test_layer_pattern_follows_the_full_attention_interval():
    assert qwen3_next.layer_types(8, 4) == ("gdn",) * 3 + ("attention",) \
        + ("gdn",) * 3 + ("attention",)
    assert ref.layer_kinds(dict(ARCH, num_hidden_layers=8)) == list(
        qwen3_next.layer_types(8, 4))
    model = build_two_tower(_config(), VOCAB)
    q, p = _ids()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), q, p)
    layers = tree["params"]["query_tower"]["layers"]
    for i in range(4):
        assert set(layers[f"block{i}_mix"]) == (
            {"ln_mix", "attn"} if i == 3 else {"ln_mix", "linear_attn"})
        assert "shared_expert_gate" in layers[f"block{i}_ffn"]["moe"]
    gdn = layers["block0_mix"]["linear_attn"]
    # 2 key heads x (16 + 16 + 2 x 8 + 2 x 8), 2 x (2 + 2); no conv bias
    assert gdn["in_proj_qkvz"]["kernel"].shape == (64, 128)
    assert gdn["in_proj_ba"]["kernel"].shape == (64, 8)
    assert set(gdn) == {"in_proj_qkvz", "in_proj_ba", "conv_kernel", "A_log",
                        "dt_bias", "norm", "out_proj"}
    assert gdn["norm"]["scale"].shape == (8,)
    attn = layers["block3_mix"]["attn"]
    assert attn["wq"]["kernel"].shape == (64, 4 * 2 * 16)      # [q | gate]
    assert attn["q_norm"]["centred_scale"].shape == (16,)


def test_projections_are_split_as_published():
    """Column c of the projections reads c: each piece is where the
    published layout puts it (per key head [q | k | v r | z r] and
    [b r | a r], value head j = key head j // r, r = 2)."""
    c = qwen3_next.Qwen3NextSizes(
        model_dim=64, num_heads=4, num_kv_heads=2, head_dim=16,
        partial_rotary_factor=0.25, rope_theta=1e7,
        full_attention_interval=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=3,
        linear_value_head_dim=5, linear_conv_kernel_dim=4, moe_mlp_dim=16,
        shared_mlp_dim=16, n_routed_experts=8, num_experts_per_tok=3,
        experts_held=8)
    Dk, Dv, per = 3, 5, 2 * 3 + 2 * 2 * 5
    qkvz = jnp.arange(2 * per, dtype=jnp.float32)[None, None]
    ba = jnp.arange(8, dtype=jnp.float32)[None, None]
    q, k, v, z, b, a = qwen3_next.split_projections(qkvz, ba, c)
    for h in range(2):
        np.testing.assert_array_equal(q[0, 0, h], h * per + np.arange(Dk))
        np.testing.assert_array_equal(k[0, 0, h],
                                      h * per + Dk + np.arange(Dk))
        for m in range(2):
            j = 2 * h + m
            np.testing.assert_array_equal(
                v[0, 0, j], h * per + 2 * Dk + m * Dv + np.arange(Dv))
            np.testing.assert_array_equal(
                z[0, 0, j], h * per + 2 * Dk + 2 * Dv + m * Dv + np.arange(Dv))
            assert float(b[0, 0, j]) == h * 4 + m
            assert float(a[0, 0, j]) == h * 4 + 2 + m


# -- the expert layer ---------------------------------------------------------

def _layer(held, start, experts=32):
    return glm_moe.RoutedExperts(
        64, 16, experts, 10, 1.0, held, start, router="softmax_topk",
        shared_dim=16, shared_gate=True, dtype=jnp.float32)


def _layer_params(seed=5, experts=32):
    s = lambda *d: jax.ShapeDtypeStruct(d, jnp.float32)
    tree = {"w_gate": s(experts, 64, 16), "w_up": s(experts, 64, 16),
            "w_down": s(experts, 16, 64), "router": {"kernel": s(64, experts)},
            "shared_expert_gate": {"kernel": s(64, 1)},
            "shared": {"wi_0": {"kernel": s(64, 16)},
                       "wi_1": {"kernel": s(64, 16)},
                       "wo_mlp": {"kernel": s(16, 64)}}}
    return wq.make_params(tree, seed)


def _share(p, start, held):
    cut = lambda w: w[start:start + held]
    return dict(p, w_gate=cut(p["w_gate"]), w_up=cut(p["w_up"]),
                w_down=cut(p["w_down"]))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips with two of 32 experts each (10 a token): their routed
    parts, plus the gated shared expert once, are the uncut reference's
    layer, and every assignment lands on exactly one share."""
    p = _layer_params()
    u = jax.random.normal(jax.random.key(1), (2, 24, 64))
    flat = u.reshape(48, 64)
    whole, counts = ref.experts(p, flat, dict(ARCH, num_experts_per_tok=10,
                                              experts_held_start=0))
    shared = ref._swiglu(p["shared"], flat, ref.identity) * jax.nn.sigmoid(
        flat @ p["shared_expert_gate"]["kernel"])
    total, held = shared, []
    for start in range(0, 32, 2):
        out, st = _layer(2, start).apply({"params": _share(p, start, 2)}, u)
        total = total + (out.reshape(48, 64) - shared)
        held.append(st["held"])
        assert int(st["dropped"]) == 0
        assert int(st["held"].sum() + st["absent"]) == 48 * 10
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(held), counts)


def test_softmax_topk_is_the_renormalised_top_k_of_a_softmax_over_all():
    """The layer's router (a softmax over the k selected logits) against the
    published one (a softmax over all experts, top k, renormalised): the
    same layer, and the same gradients for the router and the input."""
    p = _layer_params(seed=7)
    u = jax.random.normal(jax.random.key(3), (2, 24, 64))
    arch = dict(ARCH, num_experts_per_tok=10, experts_held_start=0)

    def program(params, x):
        return jnp.sum(jnp.sin(_layer(32, 0).apply({"params": params},
                                                    x)[0]))

    def published(params, x):
        return jnp.sum(jnp.sin(ref.experts(params, x.reshape(48, 64),
                                           arch)[0]))

    v1, g1 = jax.value_and_grad(program, argnums=(0, 1))(p, u)
    v2, g2 = jax.value_and_grad(published, argnums=(0, 1))(p, u)
    assert float(v1) == pytest.approx(float(v2), rel=1e-5)
    np.testing.assert_allclose(g1[0]["router"]["kernel"],
                               g2[0]["router"]["kernel"], atol=1e-5)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-5)
    chosen, weight = ref.route(p, u.reshape(48, 64), arch)
    logits = u.reshape(48, 64) @ p["router"]["kernel"]
    picked, mine = jax.lax.top_k(logits, 10)
    np.testing.assert_array_equal(chosen, mine)
    np.testing.assert_allclose(weight, jax.nn.softmax(picked, -1), rtol=1e-5)


def test_zero_centred_norm_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.key(0), (3, 8))
    norm = RmsNorm(dtype=jnp.float32, eps=1e-6, zero_centred=True)
    params = norm.init(jax.random.key(1), x)
    assert set(params["params"]) == {"centred_scale"}
    assert float(jnp.abs(params["params"]["centred_scale"]).max()) == 0.0
    w = jnp.linspace(-0.5, 0.5, 8)
    got = norm.apply({"params": {"centred_scale": w}}, x)
    rms = jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, x / rms * (1 + w), rtol=1e-5)


# -- the train step and the preset ---------------------------------------------

def test_trainer_steps_the_tower_and_counts_the_recurrence(tmp_path):
    """`Trainer.compiled_step` on the tower: the first step's loss is the
    reference's on the same rows, nothing is dropped, the linear attention's
    counters count every position of both sides in every Gated DeltaNet
    layer, and AdamW moves the new leaves (by the second step: the warm-up
    starts at 0)."""
    cfg = _config(**{"train.batch_size": 4, "train.warmup_steps": 1,
                     "model.remat_blocks": False})
    toks = (corpus.HashTokenizer(VOCAB, 16, 7, 0),
            corpus.HashTokenizer(VOCAB, 136, 7, 1))
    trainer = Trainer(cfg, corpus=corpus.IdCorpus(64), tokenizers=toks,
                      workdir=str(tmp_path))
    state = trainer.init_state()
    before = jax.tree_util.tree_map(np.asarray, state.params)
    step = trainer.compiled_step(state)
    batch = next(trainer.batches())
    q, p = np.asarray(batch["query"]), np.asarray(batch["page"])
    want, _ = _reference(before, jnp.asarray(q), jnp.asarray(p))
    state, metrics = step(state, batch, trainer.base_rng())
    assert float(metrics["loss"]) == pytest.approx(float(want), rel=1e-4)
    assert int(metrics["moe/dropped"]) == 0
    np.testing.assert_array_equal(metrics["gdn/tokens"],
                                  [4 * (16 + 136)] * 3)
    assert np.all(np.isfinite(np.asarray(metrics["gdn/state_norm_max"])))
    state, _ = step(state, batch, trainer.base_rng())
    layers = lambda t: t["params"]["query_tower"]["layers"]
    for block, path in (
            ("block0_mix", ("linear_attn", "A_log")),
            ("block0_mix", ("linear_attn", "dt_bias")),
            ("block0_mix", ("linear_attn", "conv_kernel")),
            ("block0_mix", ("ln_mix", "centred_scale")),
            ("block3_mix", ("attn", "q_norm", "centred_scale")),
            ("block3_ffn", ("moe", "shared_expert_gate", "kernel"))):
        a, b = layers(state.params)[block], layers(before)[block]
        for key in path:
            a, b = a[key], b[key]
        assert not np.array_equal(np.asarray(a), b), (block, path)


def test_preset_resolves_to_what_the_file_states():
    from benchmarks.jobs import train_qwen3_next
    cell = harness.Cell(CELL)
    cfg = train_qwen3_next.program_config(cell, seed=5)
    assert cfg.mesh.num_devices == 1 and cfg.train.batch_size == 16
    assert cfg.model.remat_blocks and cfg.model.shared_towers
    assert (cfg.data.query_len, cfg.data.page_len) == (64, 2048)
    arch = train_qwen3_next.arch_of(cell)
    assert arch["num_hidden_layers"] == 4 and arch["num_experts"] == 512
    # a published key that the preset does not carry is named in the exit
    for key, value, named in (
            ("linear_num_value_heads", 16, "linear_num_value_heads"),
            ("partial_rotary_factor", 0.5, "partial_rotary_factor"),
            ("shared_expert_intermediate_size", 1024,
             "shared_expert_intermediate_size"),
            ("norm_topk_prob", False, "built"),
            ("mlp_only_layers", [0], "built"),
            ("attn_output_gate", True, "attn_output_gate")):
        bad = json.loads(json.dumps(cell.config))
        bad["published"][key] = value
        other = harness.Cell(CELL)
        other.config = bad
        with pytest.raises(SystemExit, match=named):
            train_qwen3_next.program_config(other, seed=5)
