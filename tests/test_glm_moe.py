"""models/glm_moe.py (GLM-4.7-Flash as an embedding tower) at tiny widths:
the program against the benchmark's plain reference, the share of the
experts tied to the uncut layer, no assignment dropped, the expected-load
buffers against the worst-case ones (bit for bit, and the fallback
counted), the last-token pool, and the selection bias (it selects, never
weighs, and is held)."""
import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import weights_moe  # noqa: E402
from benchmarks.reference import glm4_moe_lite as ref  # noqa: E402
from dnn_page_vectors_tpu.config import get_config  # noqa: E402
from dnn_page_vectors_tpu.models import glm_moe  # noqa: E402
from dnn_page_vectors_tpu.models.factory import build_two_tower  # noqa: E402
from dnn_page_vectors_tpu.models.losses import (  # noqa: E402
    cosine_contrastive_loss)
from dnn_page_vectors_tpu.ops import grouped_matmul as gm  # noqa: E402
from dnn_page_vectors_tpu.train.loop import moe_metrics  # noqa: E402

# hidden 64, 8 experts of width 32, 2 a token, ranks 16 / 24, 2 heads of
# (12 + 4 | 16), 1 dense + 2 expert layers
ARCH = {"num_attention_heads": 2, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 4, "v_head_dim": 16, "kv_lora_rank": 24,
        "rms_norm_eps": 1e-5, "rope_theta": 1e6, "num_experts_per_tok": 2,
        "routed_scaling_factor": 1.8, "experts_held_start": 2,
        "num_hidden_layers": 3, "first_k_dense_replace": 1}
VOCAB = 100


def _config(dtype="float32", attention="flash", held=4, start=2, **more):
    ov = {"model.model_dim": 64, "model.mlp_dim": 96,
          "model.moe_intermediate_size": 32, "model.num_heads": 2,
          "model.q_lora_rank": 16, "model.kv_lora_rank": 24,
          "model.qk_nope_head_dim": 12, "model.qk_rope_head_dim": 4,
          "model.v_head_dim": 16, "model.n_routed_experts": 8,
          "model.num_experts_per_tok": 2, "model.num_layers": 3,
          "model.experts_held": held, "model.experts_held_start": start,
          "model.out_dim": 32, "model.dtype": dtype,
          "model.attention": attention, "data.vocab_size": VOCAB,
          "data.page_len": 32, "data.query_len": 16}
    ov.update(more)
    return get_config("glm47_flash_ep8", ov)


def _ids(seed=0):
    rng = np.random.default_rng(seed)
    q = rng.integers(1, VOCAB, (4, 16))
    q[1, 9:] = 0                                   # padding at the end
    p = rng.integers(1, VOCAB, (4, 32))
    p[2, 20:] = 0
    return jnp.asarray(q, jnp.int32), jnp.asarray(p, jnp.int32)


def _model_and_params(cfg, seed=12345):
    model = build_two_tower(cfg, VOCAB)
    q, p = _ids()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), q, p)
    return model, weights_moe.make_params(tree, seed)


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


# float32: rounding of another order of summation. bfloat16: 8 bits of
# mantissa through 3 blocks at width 64 move a vector by about 2% and a
# leaf's gradient by up to a quarter of its norm; the seed is one on which
# no token's 2nd and 3rd router scores lie within that error of each other
# (at this width one flipped expert moves a last-token vector by half).
@pytest.mark.parametrize("dtype,attention,remat,seed,tol,grad_tol", [
    ("float32", "flash", True, 12345, 1e-5, 3e-5),
    ("float32", "dense", False, 12345, 1e-5, 3e-5),
    ("bfloat16", "flash", True, 1, 0.05, 0.25)])
def test_tower_equals_the_plain_reference(dtype, attention, remat, seed, tol,
                                          grad_tol):
    cfg = _config(dtype, attention, **{"model.remat_blocks": remat})
    model, params = _model_and_params(cfg, seed)
    q, p = _ids()
    (l1, (q1, p1, st)), g1 = jax.jit(jax.value_and_grad(
        lambda v: _program(model, v, q, p), has_aux=True))(params)
    (l2, (q2, p2, counts)), g2 = jax.jit(jax.value_and_grad(
        lambda v: _reference(v, q, p), has_aux=True))(params)
    assert abs(float(l1) - float(l2)) <= tol * abs(float(l2))
    for a, b in ((q1, q2), (p1, p2)):
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max())
    norm = lambda t: float(jnp.sqrt(jnp.sum(jnp.square(t))))
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    for (path, a), b in zip(flat1, jax.tree_util.tree_leaves(g2)):
        assert norm(a - b) <= grad_tol * max(norm(b), 1e-3), \
            weights_moe.path_str(path)
    m = moe_metrics(st[glm_moe.STATS])
    assert int(m["moe/dropped"]) == 0
    assert m["moe/assignments_held"].shape == (2, 4)
    np.testing.assert_array_equal(m["moe/assignments_held"], counts)
    tokens = q.size + p.size
    np.testing.assert_array_equal(
        m["moe/assignments_held"].sum(1) + m["moe/assignments_absent"],
        [2 * tokens] * 2)


def test_rows_in_groups_give_the_same_step(monkeypatch):
    """A long batch goes through the blocks in groups of rows (a scan):
    same vectors, same counters."""
    cfg = _config()
    model, params = _model_and_params(cfg)
    q, p = _ids()
    run = lambda: jax.jit(lambda v: _program(model, v, q, p))(params)
    whole = run()
    monkeypatch.setattr(glm_moe, "_ROW_GROUP_TOKENS", 64)   # 4 x 32 -> 2 groups
    grouped = run()
    np.testing.assert_allclose(grouped[1][1], whole[1][1], rtol=1e-5,
                               atol=1e-6)
    a, b = (moe_metrics(x[1][2][glm_moe.STATS]) for x in (grouped, whole))
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])


# 2 of 8 experts held, tiles of 8, row groups of 64 tokens: the queries are
# one group (64 tokens: 6 tiles expected, 18 at worst), the pages two (10
# and 18), so a step makes 2 expert layers x 3 calls
@pytest.mark.parametrize("favoured,calls", [(0.0, 0), (100.0, 6)],
                         ids=["as_routed", "all_on_the_held_experts"])
def test_tower_counts_the_calls_that_took_the_worst_case(monkeypatch,
                                                         favoured, calls):
    monkeypatch.setattr(glm_moe, "_EXPERT_TILE", 8)
    monkeypatch.setattr(glm_moe, "_ROW_GROUP_TOKENS", 64)
    cfg = _config(held=2, **{"model.remat_blocks": True})
    model, params = _model_and_params(cfg)
    layers = params["params"]["query_tower"]["layers"]
    for name in ("block1_ffn", "block2_ffn"):
        bias = layers[name]["moe"]["select_bias"]
        layers[name]["moe"]["select_bias"] = bias.at[2:4].add(favoured)
    q, p = _ids()
    (l1, (q1, p1, st)), g1 = jax.jit(jax.value_and_grad(
        lambda v: _program(model, v, q, p), has_aux=True))(params)
    (l2, (q2, p2, counts)), g2 = jax.jit(jax.value_and_grad(
        lambda v: _reference(v, q, p), has_aux=True))(params)
    m = moe_metrics(st[glm_moe.STATS])
    assert int(m["moe/worst_case_calls"]) == calls
    assert int(m["moe/dropped"]) == 0
    np.testing.assert_array_equal(m["moe/assignments_held"], counts)
    if favoured:
        assert int(m["moe/assignments_absent"].sum()) == 0
    assert abs(float(l1) - float(l2)) <= 1e-5 * abs(float(l2))
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-5)
    norm = lambda t: float(jnp.sqrt(jnp.sum(jnp.square(t))))
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    for (path, a), b in zip(flat1, jax.tree_util.tree_leaves(g2)):
        assert norm(a - b) <= 3e-5 * max(norm(b), 1e-3), \
            weights_moe.path_str(path)


def _layer_params(seed=3, experts=8):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                               jnp.float32)
    return {"router": {"kernel": n(64, experts)},
            "select_bias": jnp.asarray(0.02 * rng.normal(size=experts),
                                       jnp.float32),
            "w_gate": n(experts, 64, 32), "w_up": n(experts, 64, 32),
            "w_down": n(experts, 32, 64),
            "shared": {"wi_0": {"kernel": n(64, 32)},
                       "wi_1": {"kernel": n(64, 32)},
                       "wo_mlp": {"kernel": n(32, 64)}}}


def _share(p, start, held):
    cut = lambda w: w[start:start + held]
    return dict(p, w_gate=cut(p["w_gate"]), w_up=cut(p["w_up"]),
                w_down=cut(p["w_down"]))


def _layer(start, held):
    return glm_moe.RoutedExperts(64, 32, 8, 2, 1.8, held, start,
                                 dtype=jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips with two experts each: their routed parts, plus the shared
    expert counted once, are the whole layer as the reference computes it."""
    p = _layer_params()
    u = jnp.asarray(np.random.default_rng(4).normal(size=(2, 24, 64)),
                    jnp.float32)
    arch = dict(ARCH, experts_held_start=0)
    whole, counts = ref._experts(p, u.reshape(48, 64), arch, ref.identity,
                                 True)
    shared = ref._swiglu(p["shared"], u.reshape(48, 64), ref.identity)
    total, held = shared, []
    for start in (0, 2, 4, 6):
        y, st = jax.jit(_layer(start, 2).apply)(
            {"params": _share(p, start, 2)}, u)
        total = total + (y.reshape(48, 64) - shared)
        held.append(st["held"])
        assert int(st["dropped"]) == 0
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(held), counts)
    assert int(counts.sum()) == 48 * 2


def _worst_case_calls(st):
    """One layer call's counters as the tower sows them, through the step's
    `moe_metrics`."""
    return int(moe_metrics({"query_tower": {k: (v[None],) for k, v in
                                            st.items()}})
               ["moe/worst_case_calls"])


# 48 tokens, 2 a token, experts 2 and 3 held of 8, tiles of 8: the expected
# buffer has 2 * 24 / 8 + 2 = 8 tiles, the worst case 14
@pytest.mark.parametrize("favoured,fallback", [((3,), 0), ((2, 3), 1)],
                         ids=["one_expert", "both_held_experts"])
def test_no_assignment_is_dropped_when_every_token_picks_one_expert(
        monkeypatch, favoured, fallback):
    monkeypatch.setattr(glm_moe, "_EXPERT_TILE", 8)
    p = _layer_params()
    for e in favoured:
        p["select_bias"] = p["select_bias"].at[e].set(100.0)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 24, 64)),
                    jnp.float32)
    y, st = _layer(2, 2).apply({"params": _share(p, 2, 2)}, u)
    assert int(st["dropped"]) == 0 and int(st["held"][1]) == 48
    assert int(st["held"].sum() + st["absent"]) == 96
    # both held experts picked by every token is the worst case itself: the
    # call takes the fallback, and says so
    assert _worst_case_calls(st) == fallback
    assert (int(st["absent"]) == 0) == bool(fallback)
    want, _ = ref._experts(_share(p, 2, 2), u.reshape(48, 64),
                           dict(ARCH, experts_held_start=2), ref.identity,
                           True)
    np.testing.assert_allclose(y.reshape(48, 64), want, rtol=1e-5, atol=1e-5)


def _steered(loads, seed=7):
    """Layer parameters and [2, 32, 64] inputs whose routing puts loads[0]
    rows on expert 2 (the first tokens) and loads[1] on expert 3 (the last
    ones), the other choices on experts 0 and 1: the router reads the first
    8 input dims, one an expert."""
    p = _layer_params(seed)
    router = np.zeros((64, 8), np.float32)
    router[np.arange(8), np.arange(8)] = 1.0
    p["router"] = {"kernel": jnp.asarray(router)}
    p["select_bias"] = jnp.zeros(8, jnp.float32)
    u = np.random.default_rng(seed).normal(size=(64, 64)).astype(np.float32)
    t = np.arange(64)
    first = np.where(t < loads[0], 2, 0)
    second = np.where(t >= 64 - loads[1], 3, 1)
    u[:, :8] = -4.0
    u[t, first], u[t, second] = 4.0, 3.0
    return _share(p, 2, 2), jnp.asarray(u.reshape(2, 32, 64))


def _value_grads_counters(params, u):
    def loss(q, v):
        y, st = _layer(2, 2).apply({"params": q}, v)
        return jnp.sum(y ** 2), (y, st)
    (_, (y, st)), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(params, u)
    return y, grads, st


# 64 tokens, 2 a token, experts 2 and 3 held of 8, tiles of 8: the expected
# buffer has 2 * 32 / 8 + 2 = 10 tiles, the worst case 18
@pytest.mark.parametrize("loads,fallback", [
    ((16, 16), 0), ((40, 40), 0), ((41, 40), 1), ((64, 64), 1)],
    ids=["under", "at", "one_row_over", "every_token_picks_held"])
def test_expected_and_worst_case_buffers_give_the_same_layer_bit_for_bit(
        monkeypatch, loads, fallback):
    monkeypatch.setattr(glm_moe, "_EXPERT_TILE", 8)
    params, u = _steered(loads)
    y, grads, st = _value_grads_counters(params, u)
    # one path, over worst-case buffers, as it was before there were two
    monkeypatch.setattr(gm, "expected_tiles",
                        lambda t, k, held, experts, tile:
                        gm.num_tiles(t, k, held, tile))
    y0, grads0, st0 = _value_grads_counters(params, u)
    np.testing.assert_array_equal(y, y0)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 9                  # 8 parameters and the input
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_array_equal(a, b, err_msg=str(path))
        if "select_bias" not in str(path):
            assert float(jnp.abs(a).max()) > 0, path
    for key in ("held", "absent", "dropped"):
        np.testing.assert_array_equal(st[key], st0[key])
    np.testing.assert_array_equal(st["held"], loads)
    assert int(st["dropped"]) == 0
    assert (_worst_case_calls(st), _worst_case_calls(st0)) == (fallback, 0)


def _conds(jaxpr, found=None):
    """Every `cond` of a jaxpr and of what it calls, a kernel's body (its
    `pl.when`) left out."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _conds(sub, found)
    return found


@pytest.mark.parametrize("held,conds", [(8, 0), (4, 0), (2, 2)])
def test_where_the_two_sizes_are_one_there_is_no_cond(monkeypatch, held,
                                                      conds):
    """Half of the experts or more: the expected-load buffer is the worst
    case's, one path and no branch; under that, one `cond` forward and one
    backward."""
    monkeypatch.setattr(glm_moe, "_EXPERT_TILE", 8)
    p = _share(_layer_params(), 0, held)
    u = jnp.zeros((2, 24, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(_layer(0, held).apply({"params": q}, u)[0])))(p)
    assert len(_conds(jaxpr.jaxpr)) == conds


def _rows_of(jaxpr, rows, seen=0):
    """How many variables of a jaxpr, and of what it calls, have `rows` as
    a dimension."""
    has = lambda v: rows in getattr(getattr(v, "aval", None), "shape", ())
    seen += sum(map(has, jaxpr.invars + jaxpr.constvars))
    for eqn in jaxpr.eqns:
        seen += sum(map(has, eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            seen = _rows_of(sub, rows, seen)
    return seen


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_no_worst_case_sized_array_on_the_expected_path_at_the_cells_widths(
        remat):
    """A row group of `glm47_flash_ep8` (4,096 tokens, 8 of 64 experts of
    width 1,536 on a hidden 2,048, 4 a token), shapes only: the worst case's
    18,432 rows appear in the fallback's branches alone, not in the
    expected branch, nor in what a `cond` takes or hands out (JAX would put
    each branch's residuals there, as zeros from the other branch)."""
    layer = glm_moe.RoutedExperts(2048, 1536, 64, 4, 1.8, 8, 0,
                                  dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((4, 1024, 2048), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert gm.num_tiles(4096, 4, 8, 256) * 256 == 18432
    assert gm.expected_tiles(4096, 4, 8, 64, 256) * 256 == 6144
    apply = jax.checkpoint(layer.apply) if remat else layer.apply
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, v: jnp.sum(apply(p, v)[0].astype(jnp.float32)),
        (0, 1)))(params, x).jaxpr
    conds = _conds(jaxpr)
    assert len(conds) == 2                              # forward, backward
    for eqn in conds:
        worst, expected = (b.jaxpr for b in eqn.params["branches"])
        assert _rows_of(worst, 18432) > 0
        assert _rows_of(expected, 18432) == 0 and _rows_of(expected, 6144) > 0
        for v in eqn.invars + eqn.outvars:
            assert 18432 not in v.aval.shape, v.aval
    # and nowhere outside the two conds
    outside = _rows_of(jaxpr, 18432) - sum(
        _rows_of(b.jaxpr, 18432) for eqn in conds
        for b in eqn.params["branches"])
    assert outside == 0


def _calls(jaxpr, counts=None):
    """Pallas calls by kernel name, and `dot_general`s outside kernels, in
    a jaxpr and in what it calls."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
            continue
        counts["dot_general"] += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, counts)
    return counts


# Every value a half block names for its recomputation, by group, and the
# products (or the kernel) a kept group spares the second forward at one
# site of one layer
NAMED = {"flash": ("flash_out", "flash_lse"),
         "dense": ("mlp_gate", "mlp_up"),
         "shared": ("shared_gate", "shared_up"),
         "latents": ("mla_q_a", "mla_kv_a")}
EVERY_NAME = sum(NAMED.values(), ())


def _groups_kept():
    assert set(glm_moe._KEPT) <= set(EVERY_NAME)
    return {g for g, names in NAMED.items()
            if set(names) <= set(glm_moe._KEPT)}


# the file's widths; 3 layers (1 dense, 2 expert) x (queries + pages) = 6
# sites of causal flash (the pages' groups are one scan body). `cond`: 2 of
# 8 experts held, so the routed part is `_routed`, whose backward runs its
# own forward again. bfloat16: a kept value is rounded to its 8 bits of
# mantissa where XLA lets a value it makes again ride in float32 through
# the fusion that reads it, so there the gradients agree to rounding (2.3%
# of a leaf's norm at most over 3 seeds) and not to the bit; the loss does
@pytest.mark.parametrize("dtype,row_group,held,grad_tol,every", [
    ("float32", 4096, 4, 0, False), ("float32", 4096, 4, 0, True),
    ("float32", 64, 4, 0, True), ("bfloat16", 4096, 4, 0.05, True),
    ("float32", 64, 2, 0, False)],
    ids=["as_listed", "every_name", "row_groups", "bfloat16", "cond"])
def test_a_recomputed_half_keeps_the_named_values(monkeypatch, dtype,
                                                  row_group, held, grad_tol,
                                                  every):
    """With `remat_blocks` the second forward of a half block makes nothing
    again that `_KEPT` lists: no product of a listed SwiGLU pair or latent,
    and with flash's pair listed no second `flash_fwd` (one forward a site,
    as many as `flash_dq` and `flash_dkv`), where a recomputation that
    keeps nothing launches two. Loss and every gradient leaf are that
    recomputation's bit for bit in float32, a kept value being the bits
    the second forward would have made. `every`: with every name the
    towers give listed, whatever the tree's list admits."""
    monkeypatch.setattr(glm_moe, "_ROW_GROUP_TOKENS", row_group)
    if held == 2:
        monkeypatch.setattr(glm_moe, "_EXPERT_TILE", 8)
    if every:
        monkeypatch.setattr(glm_moe, "_KEPT", EVERY_NAME)
    groups = _groups_kept()
    cfg = _config(dtype, held=held, **{"model.remat_blocks": True})
    model, params = _model_and_params(cfg)
    q, p = _ids()

    def trace_and_run():
        # a function of its own each time: a trace is cached by function
        step = jax.value_and_grad(lambda v: _program(model, v, q, p)[0])
        return (_calls(jax.make_jaxpr(step)(params).jaxpr),
                jax.jit(step)(params))

    kept, (loss, grads) = trace_and_run()
    monkeypatch.setattr(glm_moe, "_KEPT", ())
    nothing, (loss0, grads0) = trace_and_run()
    assert nothing["flash_fwd"] == 12
    assert kept["flash_fwd"] == (6 if "flash" in groups else 12)
    for calls in (kept, nothing):
        assert (calls["flash_dq"], calls["flash_dkv"]) == (6, 6)
        for name in ("moe_gmm", "moe_tgmm"):     # the routed part: as before
            assert calls[name] == nothing[name] > 0
    # gate and up of 1 dense and 2 expert layers, `wq_a` and `wkv_a` of 3,
    # at 2 sites each
    spared = 2 * (2 * 1 * ("dense" in groups) + 2 * 2 * ("shared" in groups)
                  + 2 * 3 * ("latents" in groups))
    assert nothing["dot_general"] - kept["dot_general"] == spared
    assert float(loss) == float(loss0)
    norm = lambda t: float(jnp.sqrt(jnp.sum(jnp.square(t))))
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(grads0)):
        assert norm(a - b) <= grad_tol * max(norm(b), 1e-3), \
            weights_moe.path_str(path)


@pytest.mark.parametrize("kept", ["as_listed", "every_name", "nothing"])
def test_what_a_recomputed_block_keeps_at_the_cells_widths(monkeypatch, kept):
    """One row group of `glm47_flash_ep8` through a dense and an expert
    block, shapes only: every value of a token's size or more that the
    forward hands the backward, in bytes a token. The figures are those of
    PERF.md's memory reckoning (section 6, PR 36)."""
    from jax._src.ad_checkpoint import saved_residuals
    from dnn_page_vectors_tpu.models.factory import _build_encoder
    if kept != "as_listed":
        monkeypatch.setattr(glm_moe, "_KEPT",
                            EVERY_NAME if kept == "every_name" else ())
    tower = _build_encoder(get_config("glm47_flash_ep8"), 19360, "tower")
    blocks = glm_moe.Blocks(tower.sizes, 2, remat=True, dtype=tower.dtype)
    B, L = 4, 1024
    x = jax.ShapeDtypeStruct((B, L, 2048), jnp.bfloat16)
    mask = jnp.ones((B, L), bool)
    params = jax.eval_shape(blocks.init, jax.random.PRNGKey(0), None,
                            (x, mask))
    saved = saved_residuals(
        lambda v, x: blocks.apply(v, None, (x, mask))[1][0], params, x)
    found = collections.Counter(
        (aval.shape, aval.size * aval.dtype.itemsize // (B * L))
        for aval, src in saved
        if "from the argument" not in src and aval.size >= B * L)
    # a half's input (the block's own input is an argument) and the mask
    want = collections.Counter({((B, L, 2048), 4096): 3, ((B, L), 1): 1})
    # a group's values over the two blocks, and the sites that make them
    sizes = {"flash": ({((B, 20, L, 256), 10240): 2, ((B, 20, L), 80): 2}, 2),
             "dense": ({((B, L, 10240), 20480): 2}, 1),
             "shared": ({((B * L, 1536), 3072): 2}, 1),
             "latents": ({((B, L, 768), 1536): 2, ((B, L, 576), 1152): 2}, 2)}
    per_token = {g: sum(size * n for (_, size), n in values.items()) // sites
                 for g, (values, sites) in sizes.items()}
    assert per_token == {"flash": 10320, "dense": 40960, "shared": 6144,
                         "latents": 2688}
    for group in _groups_kept():
        want.update(sizes[group][0])
    assert found == want


def test_the_bias_selects_and_never_weighs():
    p = _layer_params()
    u = jnp.asarray(np.random.default_rng(6).normal(size=(1, 24, 64)),
                    jnp.float32)
    arch = dict(ARCH, experts_held_start=0)
    chosen0, weight0 = ref.route(p, u[0], arch)
    moved = dict(p, select_bias=p["select_bias"].at[5].add(0.3))
    chosen1, weight1 = ref.route(moved, u[0], arch)
    assert (chosen0 != chosen1).any()                     # it selects
    same = (chosen0 == chosen1).all(axis=1)
    assert same.any()
    np.testing.assert_array_equal(weight0[same], weight1[same])   # only that
    # the program agrees, and no gradient reaches the bias
    run = lambda q: _layer(0, 8).apply({"params": q}, u)[0]
    np.testing.assert_allclose(
        jax.jit(run)(moved).reshape(24, 64),
        ref._experts(moved, u[0], arch, ref.identity, True)[0],
        rtol=1e-5, atol=1e-5)
    g = jax.jit(jax.grad(lambda q: jnp.sum(run(q) ** 2)))(p)
    assert float(jnp.abs(g["select_bias"]).max()) == 0.0
    assert float(jnp.abs(g["router"]["kernel"]).max()) > 0.0


def test_a_train_step_leaves_the_bias_where_it_was(tmp_path):
    from benchmarks import corpus
    from dnn_page_vectors_tpu.train.loop import Trainer
    cfg = _config(attention="dense",
                  **{"train.batch_size": 4, "mesh.data": 1,
                     "model.remat_blocks": False,
                     "train.learning_rate": 1e-2, "train.warmup_steps": 1})
    toks = tuple(corpus.HashTokenizer(VOCAB, n, 7, side)
                 for side, n in enumerate((16, 32)))
    trainer = Trainer(cfg, corpus=corpus.IdCorpus(64), tokenizers=toks,
                      workdir=str(tmp_path))
    state = trainer.init_state()
    bias = lambda s: np.asarray(
        s.params["params"]["query_tower"]["layers"]["block1_ffn"]["moe"][
            "select_bias"])
    gate = lambda s: np.asarray(
        s.params["params"]["query_tower"]["layers"]["block1_ffn"]["moe"][
            "w_gate"])
    b0, g0 = bias(state), gate(state)
    step, rng, batches = (trainer.compiled_step(state), trainer.base_rng(),
                          trainer.batches())
    for _ in range(3):
        state, metrics = step(state, next(batches), rng)
    batches.close()
    np.testing.assert_array_equal(bias(state), b0)     # update exactly zero
    assert np.abs(gate(state) - g0).max() > 0          # the rest trains
    assert float(metrics["moe/dropped"]) == 0.0
    assert metrics["moe/assignments_held"].shape == (2, 4)
    assert metrics["moe/assignments_absent"].shape == (2,)


@pytest.mark.parametrize("lengths", [[5, 1, 8], [8, 8, 0]])
def test_last_token_pool_picks_the_last_non_pad_position(lengths):
    x = jnp.arange(3 * 8 * 2, dtype=jnp.float32).reshape(3, 8, 2)
    mask = jnp.arange(8)[None, :] < jnp.asarray(lengths)[:, None]
    got = glm_moe.last_token(x, mask)
    want = [x[i, max(n - 1, 0)] for i, n in enumerate(lengths)]
    np.testing.assert_array_equal(got, jnp.stack(want))


def test_rope_rotates_pairs_half_a_width_apart_and_keeps_norms():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 2, 8)),
                    jnp.float32)
    y = glm_moe.rope(x, 1e6)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)   # position 0
    pair = lambda t, i: t[..., i] ** 2 + t[..., i + 4] ** 2
    for i in range(4):
        np.testing.assert_allclose(pair(y, i), pair(x, i), rtol=1e-5)
    np.testing.assert_allclose(y, ref._rope(x, 1e6), atol=1e-6)


def test_rms_norm_eps_is_a_field_and_defaults_to_1e_6():
    from dnn_page_vectors_tpu.models.transformer import RmsNorm
    assert RmsNorm().eps == 1e-6
    x = jnp.full((1, 4), 1e-3, jnp.float32)
    out = lambda eps: RmsNorm(dtype=jnp.float32, eps=eps).apply(
        {"params": {"scale": jnp.ones(4)}}, x)
    np.testing.assert_allclose(out(1e-6), x / np.sqrt(1e-6 + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(out(1e-5), x / np.sqrt(1e-6 + 1e-5), rtol=1e-6)


def test_expert_rule_shards_the_stacked_kernels_where_the_mesh_has_the_axis():
    from jax.sharding import Mesh, PartitionSpec as P
    from dnn_page_vectors_tpu.parallel import sharding
    path = "params/query_tower/layers/block1_ffn/moe/w_gate"
    assert sharding.spec_for_param(path) == P("expert", None, None)
    assert sharding.spec_for_param(path.replace("w_gate", "router/kernel")) \
        == P()
    devs = np.asarray(jax.devices()[:2])
    tree = {"params": {"query_tower": {"layers": {"block1_ffn": {"moe": {
        "w_gate": jnp.zeros((2, 4, 4))}}}}}}
    leaf = lambda mesh: jax.tree_util.tree_leaves(
        sharding.param_shardings(tree, mesh))[0].spec
    assert leaf(Mesh(devs.reshape(2, 1, 1), ("data", "model", "seq"))) \
        == P(None, None, None)                     # no such axis: held whole
    assert leaf(Mesh(devs, ("expert",))) == P("expert", None, None)


def test_preset_states_the_published_widths_and_the_share():
    m = get_config("glm47_flash_ep8").model
    assert (m.model_dim, m.num_heads, m.mlp_dim, m.moe_intermediate_size) \
        == (2048, 20, 10240, 1536)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (768, 512, 192, 64, 256)
    assert (m.n_routed_experts, m.num_experts_per_tok,
            m.routed_scaling_factor, m.first_k_dense_replace) \
        == (64, 4, 1.8, 1)
    assert (m.num_layers, m.experts_held, m.shared_towers, m.attention) \
        == (5, 8, True, "flash")
    from dnn_page_vectors_tpu.utils.flops import train_flops_per_pair
    assert train_flops_per_pair(get_config("glm47_flash_ep8"), 32) > 1e12
