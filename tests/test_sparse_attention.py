"""Attention over the keys an indexer selects (ops/sparse_attention.py,
ops/sparse_attention_kernels.py), the experts held by share
(parallel/moe.py:HeldExpertsLayer) and the model they make
(models/keye.py): the selection's exactness and tie rule, the kernels
against the plain XLA form (interpret mode on the CPU), nothing dropped
whatever the routing, the same selection in a recomputed forward, and
what a block's recomputation keeps of its kernels' work (ops/remat.py)."""

from __future__ import annotations

import collections
import importlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.keye import Keye, KeyeConfig
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.ops.remat import Kept, resolve_remat_policy
from dlrover_tpu.parallel.moe import (
    HeldExpertsConfig,
    HeldExpertsLayer,
    held_assignments,
    route_top_k,
)

sparse = importlib.import_module("dlrover_tpu.ops.sparse_attention")
kernels = importlib.import_module("dlrover_tpu.ops.sparse_attention_kernels")


@pytest.fixture()
def small_blocks(monkeypatch):
    """Block sizes at which a 256-long sequence takes several blocks of
    every kernel, and the KL kernel two slices of rows a block (they are
    read when a call is traced)."""
    jax.clear_caches()
    monkeypatch.setattr(kernels, "SELECT_BLOCK_Q", 64)
    monkeypatch.setattr(kernels, "SELECT_BLOCK_K", 128)
    monkeypatch.setattr(kernels, "KL_BLOCK", 128)
    monkeypatch.setattr(kernels, "KL_ROWS", 64)
    monkeypatch.setattr(kernels, "DEFAULT_BLOCK_Q", 128)
    monkeypatch.setattr(kernels, "DEFAULT_BLOCK_K", 128)
    yield
    jax.clear_caches()


def _operands(seq, dtype=jnp.float32, batch=2, heads=4, kv_heads=2, d=32,
              index_heads=2, index_dim=16, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (batch, heads, seq, d)).astype(dtype)
    k = jax.random.normal(keys[1], (batch, kv_heads, seq, d)).astype(dtype)
    v = jax.random.normal(keys[2], (batch, kv_heads, seq, d)).astype(dtype)
    qi = jax.random.normal(
        keys[3], (batch, seq, index_heads, index_dim)).astype(dtype)
    ki = jax.random.normal(keys[4], (batch, seq, index_dim)).astype(dtype)
    w = jax.random.normal(keys[5], (batch, seq, index_heads)) * 0.2
    return q, k, v, qi, ki, w


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_every_key_while_fewer_than_topk_then_exactly_topk(impl,
                                                           small_blocks):
    seq, topk = 256, 48
    *_, qi, ki, w = _operands(seq)
    chosen = np.asarray(sparse.selection(qi, ki, w, topk, impl=impl))
    causal = np.tril(np.ones((seq, seq), bool))
    assert not (chosen & ~causal).any()
    assert (chosen[:, :topk] == causal[:topk]).all()
    assert (chosen.sum(-1) == np.minimum(np.arange(seq) + 1, topk)).all()
    # and they are the largest: no key left out scores above one taken
    scores = np.asarray(sparse.index_scores(qi, ki, w))
    taken = np.where(chosen, scores, np.inf).min(-1)
    left = np.where(causal & ~chosen, scores, -np.inf).max(-1)
    assert (left <= taken).all()


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_ties_go_to_the_lower_position(impl, small_blocks):
    """Keys 5, 9, 40 and 200 are one vector, so every query scores them
    alike; with all other scores pushed below them and topk 3, a query
    past 40 takes 5, 9 and 40 and never 200."""
    seq, topk = 256, 3
    *_, qi, ki, w = _operands(seq)
    qi, w = jnp.abs(qi), jnp.abs(w)
    ki = -jnp.abs(ki)                       # every score 0 ...
    for s in (5, 9, 40, 200):
        ki = ki.at[:, s].set(1.0)           # ... but the planted keys'
    chosen = np.asarray(sparse.selection(qi, ki, w, topk, impl=impl))
    for t in (41, 100, 199, 200, 255):
        assert sorted(np.flatnonzero(chosen[0, t])) == [5, 9, 40], t
    assert sorted(np.flatnonzero(chosen[0, 20])) == [0, 5, 9]   # zeros tie
    assert sorted(np.flatnonzero(chosen[1, 8])) == [0, 1, 5]


def test_the_kernels_select_what_the_plain_form_selects(small_blocks):
    *_, qi, ki, w = _operands(256, jnp.bfloat16, seed=7)
    ki = ki.at[:, 5].set(ki[:, 9]).at[:, 130].set(ki[:, 9])   # real ties
    for topk in (1, 48, 128, 255, 256, 1000):
        plain = sparse.selection(qi, ki, w, topk, impl="xla")
        mine = sparse.selection(qi, ki, w, topk, impl="kernel")
        assert bool(jnp.all(plain == mine)), topk


def test_the_kernels_against_the_plain_form(small_blocks):
    """Output, KL term and every gradient, float32 operands: the Pallas
    form (masked flash kernels, `indexer_kl` with its gradient) is the
    plain form's mathematics."""
    seq, topk = 256, 48
    operands = _operands(seq)

    def both(impl):
        def objective(q, k, v, qi, ki, w):
            out, kl = sparse.sparse_attention(q, k, v, qi, ki, w, topk,
                                              impl=impl)
            return jnp.sum(out ** 2) * 1e-3 + kl, (out, kl)
        return jax.value_and_grad(objective, argnums=tuple(range(6)),
                                  has_aux=True)(*operands)

    (_, (out_x, kl_x)), grads_x = both("xla")
    (_, (out_k, kl_k)), grads_k = both("kernel")
    np.testing.assert_allclose(out_k, out_x, atol=2e-6)
    assert float(kl_x) > 0.05
    assert float(kl_k) == pytest.approx(float(kl_x), rel=1e-5)
    for name, mine, plain in zip("q k v qi ki w".split(), grads_k, grads_x):
        scale = float(jnp.max(jnp.abs(plain)))
        assert scale > 0, name
        np.testing.assert_allclose(mine, plain, atol=5e-3 * scale,
                                   err_msg=name)
    # the attention's loss reaches q, k, v alone; the KL term qi, ki, w
    def kl_only(q, k, v, qi, ki, w):
        return sparse.sparse_attention(q, k, v, qi, ki, w, topk,
                                       impl="kernel")[1]
    grads = jax.grad(kl_only, argnums=(0, 1, 2))(*operands)
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in grads)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


@pytest.mark.parametrize("case", ["float32", "first_block_empty",
                                  "bfloat16"])
def test_the_kl_kernel_forms_the_objective_and_its_gradient(case,
                                                            small_blocks):
    """`indexer_kl` takes d KL / d I back to qi, w and ki in the launch that
    forms the KL rows: its value and its three gradients are `jax.grad` of
    the plain form's KL term, over three row blocks (d KL / d ki summed
    across them), with a row block whose first key block holds no selected
    key, and in bfloat16 (d KL / d I cast for the products only). Outside
    differentiation the launch has the rows for its only output."""
    seq, topk = 384, 48
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    q, k, v, qi, ki, w = _operands(seq, dtype, seed=3)
    if case == "first_block_empty":
        # keys 0..127 score 0 under every head; later keys positive or not
        qi, w = jnp.abs(qi), jnp.abs(w)
        ki = ki.at[:, :128].set(-jnp.abs(ki[:, :128]))
        chosen = np.asarray(sparse.selection(qi, ki, w, topk, impl="xla"))
        assert not chosen[:, 256:, :128].any()
        assert chosen[:, 128:256, :128].any()

    def kl(impl):
        def of(qi, ki, w):
            return sparse.sparse_attention(q, k, v, qi, ki, w, topk,
                                           impl=impl)[1]
        return jax.value_and_grad(of, argnums=(0, 1, 2))(qi, ki, w)

    (kl_x, grads_x), (kl_k, grads_k) = kl("xla"), kl("kernel")
    exact = dtype == jnp.float32
    assert float(kl_k) == pytest.approx(float(kl_x), rel=1e-5 if exact
                                        else 2e-2)
    for name, mine, plain in zip(("qi", "ki", "w"), grads_k, grads_x):
        assert mine.dtype == plain.dtype, name
        mine, plain = (np.asarray(g, np.float32) for g in (mine, plain))
        scale = float(np.max(np.abs(plain)))
        assert scale > 0, name
        np.testing.assert_allclose(mine, plain, err_msg=name,
                                   atol=(1e-5 if exact else 3e-2) * scale)
    # the primal alone: one output, no gradient formed and thrown away
    qi_t = qi.transpose(0, 2, 1, 3)
    mask, lse_i = kernels.indexer_select(qi, ki, w, topk)
    _, lse = kernels.masked_attention(q, k, v, mask, 32 ** -0.5)
    args = (qi_t, ki, w.astype(jnp.float32), mask, lse_i, q, k, lse)
    launches = [eqn for eqn in _equations(jax.make_jaxpr(
        lambda *a: kernels.indexer_kl(*a, 32 ** -0.5))(*args).jaxpr)
        if eqn.primitive.name == "pallas_call"]
    assert [(eqn.params["name"], len(eqn.outvars)) for eqn in launches] == [
        (kernels.KERNEL_KL, 1)]
    assert float(kernels.indexer_kl(*args, 32 ** -0.5)) == pytest.approx(
        float(kl_k), rel=1e-6)


def test_a_row_with_no_selected_key_in_its_first_block(small_blocks):
    """Under the causal mask alone key 0 serves every row; a selection may
    leave a row's first block empty, and its masked scores must not count
    (NEG_INF - NEG_INF is 0)."""
    flash = importlib.import_module("dlrover_tpu.ops.flash_attention")
    seq = 256
    q, k, v, *_ = _operands(seq)
    mask = np.zeros((2, seq, seq), bool)
    for t in range(seq):
        mask[:, t, max(0, t - 2):t + 1] = True      # the last three keys
    out, _ = flash._flash_fwd(q, k, v, 32 ** -0.5, True, 128, 128,
                              mask=jnp.asarray(mask, jnp.int8))
    kk, vv = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * 32 ** -0.5
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), -1)
    np.testing.assert_allclose(
        out, jnp.einsum("bhqk,bhkd->bhqd", probs, vv), atol=2e-6)


# -- experts held by share ---------------------------------------------------


def _layer(first=2, held=4, experts=8, top_k=2, hidden=32, width=48):
    cfg = HeldExpertsConfig(num_experts=experts, experts_held=held,
                            first_expert=first, top_k=top_k,
                            hidden_size=hidden, expert_intermediate=width)
    layer = HeldExpertsLayer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, hidden))
    params = nn.unbox(layer.init(jax.random.PRNGKey(1), x))["params"]
    return cfg, layer, x, params


def _dense(cfg, params, x):
    """Every held expert on every token under a 0/1 assignment mask."""
    t = x.reshape(-1, x.shape[-1])
    gates, experts = route_top_k(
        jnp.dot(t, params["router"], precision="highest"), cfg.top_k,
        cfg.norm_topk_prob)
    out = jnp.zeros_like(t)
    for j in range(cfg.experts_held):
        weight = jnp.sum(jnp.where(experts == cfg.first_expert + j, gates,
                                   0.0), -1)
        act = jax.nn.silu(t @ params["w1"][j]) * (t @ params["w3"][j])
        out = out + weight[:, None] * (act @ params["w2"][j])
    return out.reshape(x.shape)


@pytest.mark.parametrize("to", ["one_held", "none_held", "as_it_falls"])
def test_nothing_dropped_whatever_the_routing(to):
    """The router skewed so that every token's first choice is one held
    expert: all 48 tokens reach it and come back (a capacity would drop
    most); skewed to an absent pair: nothing is computed, the output is
    exactly zero and so is every expert's gradient."""
    cfg, layer, x, params = _layer()
    router = np.array(params["router"])
    if to == "one_held":
        x = jnp.abs(x)
        router[:, 3] += 10.0           # held: experts 2..5
    if to == "none_held":
        x = jnp.abs(x)
        router[:, 0] += 10.0
        router[:, 7] += 9.0
    params = dict(params, router=jnp.asarray(router))

    def run(p):
        out, sown = layer.apply({"params": p}, x, mutable=["counters"])
        return jnp.sum(out ** 2), (out, sown)

    (_, (out, sown)), grads = jax.value_and_grad(run, has_aux=True)(params)
    np.testing.assert_allclose(out, _dense(cfg, params, x), atol=1e-6)
    _, experts = route_top_k(
        x.reshape(-1, 32) @ params["router"], cfg.top_k, True)
    held, order, sizes = held_assignments(experts, cfg.first_expert,
                                          cfg.experts_held)
    load = sown["counters"]["moe_load_max_over_mean"][0]
    if to == "one_held":
        assert int(sizes[1]) == 48                  # all of them, kept
        assert float(load) >= 2.0
        assert float(jnp.max(jnp.abs(out))) > 0
    if to == "none_held":
        assert int(jnp.sum(sizes)) == 0 and not bool(jnp.any(held))
        assert float(jnp.max(jnp.abs(out))) == 0.0
        for name in ("w1", "w2", "w3"):
            assert float(jnp.max(jnp.abs(grads[name]))) == 0.0
    assert int(jnp.sum(sizes)) == int(jnp.sum(held))
    assert sorted(np.asarray(order)) == list(range(96))
    # the second counter: the held rows over every assignment
    assert float(sown["counters"]["moe_held_rows_share"][0]) == (
        pytest.approx(int(jnp.sum(sizes)) / 96))


def test_expert_leaves_are_stacks_of_three_axes():
    _, _, _, params = _layer()
    assert params["router"].shape == (32, 8)
    assert params["router"].dtype == jnp.float32
    assert params["w1"].shape == params["w3"].shape == (4, 32, 48)
    assert params["w2"].shape == (4, 48, 32)


# -- the model ----------------------------------------------------------------


def _model(**kw):
    cfg = KeyeConfig.tiny(dtype=jnp.float32, norm_impl="reference",
                          embed_impl="gather", **kw)
    model = Keye(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 256)
    params = nn.unbox(model.init(jax.random.PRNGKey(1), tokens))["params"]
    return cfg, model, tokens, params


def _objective(model, tokens):
    def objective(p):
        (hidden, head), sown = model.apply(
            {"params": p}, tokens, mutable=["losses", "counters"],
            method="hidden_and_head")
        extra = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown["losses"]))
        return jnp.mean(jnp.dot(hidden, head) ** 2) + extra
    return objective


POLICIES = ["kernel_outputs", "nothing_saveable"]


@pytest.mark.parametrize("policy", POLICIES)
def test_the_recomputed_forward_selects_the_same_keys(policy):
    """Recomputation by block: the selection is a function of the saved
    block input alone (or, under `kernel_outputs`, kept), so the backward
    pass attends the same keys and the gradients are the same to the last
    digit, whatever the policy keeps."""
    cfg, model, tokens, params = _model(remat=False, num_layers=1)
    loss, grads = jax.value_and_grad(_objective(model, tokens))(params)
    _, again, _, _ = _model(remat=True, remat_policy=policy, num_layers=1)
    loss_again, grads_again = jax.value_and_grad(
        _objective(again, tokens))(params)
    assert float(loss) == float(loss_again)
    for (path, mine), theirs in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(grads_again)):
        np.testing.assert_array_equal(mine, theirs, err_msg=str(path))
    assert cfg.index_topk < 64


@pytest.mark.parametrize("policy, selections", [
    (None, 1), ("nothing_saveable", 2)], ids=["default", "nothing_saveable"])
def test_a_recomputed_block_keeps_its_selection(policy, selections):
    """`KeyeConfig`'s default policy keeps what the attention tagged
    (`ops/remat.py:Kept`): the gradient's program selects once a layer,
    where `nothing_saveable` selects again in the backward pass. The
    router's own `top_k` (2 of 8 experts) is recomputed under either."""
    kw = {} if policy is None else {"remat_policy": policy}
    cfg, model, tokens, params = _model(remat=True, num_layers=1, **kw)
    assert cfg.remat_policy == (policy or "kernel_outputs")
    text = str(jax.make_jaxpr(jax.grad(_objective(model, tokens)))(params))
    by_k = collections.Counter(re.findall(r"\btop_k\[[^\]]*?\bk=(\d+)", text))
    assert by_k == {str(cfg.index_topk): selections,
                    str(cfg.experts_per_token): 2}


def test_a_block_that_tags_nothing_is_recomputed_whole():
    """The policy follows what the block contains, not a model's name: a
    `Llama` tags nothing, so `kernel_outputs` gives the gradient's program
    `nothing_saveable` gives, to the letter."""
    texts = []
    for policy in POLICIES:
        model = Llama(LlamaConfig.tiny(
            dtype=jnp.float32, norm_impl="reference", attn_impl="reference",
            remat=True, remat_policy=policy))
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 256)
        params = nn.unbox(model.init(jax.random.PRNGKey(1), tokens))["params"]
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: jnp.mean(model.apply({"params": p}, tokens) ** 2)))(
                params))
        # the policy is a function and prints as its address
        texts.append(re.sub(r"policy=<[^\n]*", "policy=_", text))
    assert "policy=_" in texts[0]
    assert texts[0] == texts[1]
    assert LlamaConfig().remat_policy == "nothing_saveable"


@pytest.mark.parametrize("policy, forward_launches", [
    ("kernel_outputs", 1), ("nothing_saveable", 2)])
def test_the_kernels_run_once_under_a_policy_that_keeps_their_outputs(
        policy, forward_launches, small_blocks):
    """The Pallas form under `jax.checkpoint`: with `Kept`'s names kept,
    the selection, the masked forward and the KL kernel each stand once in
    the gradient's program (the tags sit inside the `custom_vjp`s' forward
    rules, where outputs and residuals are the same arrays; the KL kernel
    forms the term's gradient in the same launch), and the attention's two
    gradient kernels once under either policy; the gradients are the plain
    ones to the last digit."""
    operands = _operands(256)

    def objective(*operands):
        out, kl = sparse.sparse_attention(*operands, 48, impl="kernel")
        return jnp.sum(out ** 2) * 1e-3 + kl

    every = tuple(range(6))
    kept = jax.grad(jax.checkpoint(
        objective, policy=resolve_remat_policy(policy)), argnums=every)
    launches = collections.Counter(re.findall(
        r"\bname=(\w+)", str(jax.make_jaxpr(kept)(*operands))))
    for kernel in (kernels.KERNEL_SELECT, "sparse_attn_fwd",
                   kernels.KERNEL_KL):
        assert launches[kernel] == forward_launches, kernel
    for kernel in ("sparse_attn_dq", "sparse_attn_dkv"):
        assert launches[kernel] == 1, kernel
    assert not launches["indexer_dq"] and not launches["indexer_dk"]
    assert all(launches[name] for name in Kept.INDEXED)  # every tag is there
    for mine, plain in zip(kept(*operands),
                           jax.grad(objective, argnums=every)(*operands)):
        np.testing.assert_array_equal(mine, plain)


def test_the_two_objectives_train_apart():
    """The language-model loss trains everything but the indexer; the KL
    term, sown once a layer, trains the indexer alone."""
    cfg, model, tokens, params = _model()

    def parts(p):
        (hidden, head), sown = model.apply(
            {"params": p}, tokens, mutable=["losses", "counters"],
            method="hidden_and_head")
        return (jnp.mean(jnp.dot(hidden, head) ** 2),
                sum(jnp.sum(leaf) for leaf in jax.tree.leaves(
                    sown["losses"])), sown)

    lm = jax.grad(lambda p: parts(p)[0])(params)
    kl = jax.grad(lambda p: parts(p)[1])(params)
    sown = parts(params)[2]
    assert len(jax.tree.leaves(sown["losses"])) == cfg.num_layers
    for path, leaf in jax.tree_util.tree_flatten_with_path(lm)[0]:
        indexer = "indexer" in jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) == 0.0) is indexer, path
    for path, leaf in jax.tree_util.tree_flatten_with_path(kl)[0]:
        indexer = "indexer" in jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) > 0.0) is indexer, path
    assert params["layer_0"]["attn"]["q_proj"]["kernel"].shape == (64, 128)
    assert cfg.head_dim == 32 != cfg.hidden_size // cfg.num_heads


def test_head_dim_is_declarable_and_defaults_to_hidden_over_heads():
    from dlrover_tpu.models.llama import Llama, LlamaConfig

    assert LlamaConfig.tiny().head_dim == 16
    wide = LlamaConfig.tiny(attn_head_dim=32, qk_norm=True,
                            attn_impl="reference", norm_impl="reference")
    assert wide.head_dim == 32
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(Llama(wide).init(jax.random.PRNGKey(0),
                                       tokens))["params"]
    attn = params["layer_0"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (64, 128)
    assert attn["o_proj"]["kernel"].shape == (128, 64)
    assert attn["q_norm"]["weight"].shape == (32,)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == (
        wide.param_count())
    plain = nn.unbox(Llama(LlamaConfig.tiny(
        attn_impl="reference", norm_impl="reference")).init(
            jax.random.PRNGKey(0), tokens))["params"]
    assert "q_norm" not in plain["layer_0"]["attn"]


def test_the_loop_asks_the_model_for_its_flops():
    from dlrover_tpu.obs import mfu

    cfg = KeyeConfig.tiny()
    assert mfu.model_flops_per_token(cfg, cfg.param_count(), 64) == (
        cfg.flops_per_token(64))
    # of two experts a token, half of the eight are held: one in expectation
    one_expert = 3 * 64 * 32
    more = KeyeConfig.tiny(experts_held=8, first_expert=0)
    assert more.flops_per_token(64) - cfg.flops_per_token(64) == (
        pytest.approx(2 * 6.0 * one_expert))
    # the selected pairs, not the causal ones
    assert (KeyeConfig.tiny(index_topk=64).flops_per_token(64)
            > cfg.flops_per_token(64))
    # a config that cannot answer: the guess from its attribute names
    from dlrover_tpu.models.llama import LlamaConfig

    llama = LlamaConfig.tiny(embed_impl="gather")
    assert mfu.model_flops_per_token(llama, 1000.0, 128) == (
        mfu.flops_per_token(1000.0, num_layers=2, hidden_size=64,
                            seq_len=128,
                            uncounted_embed_params=256 * 64))
    assert mfu.model_flops_per_token(None, 1000.0, 128) == 6000.0


def test_a_steps_counters_reach_the_window_without_a_wait():
    from dlrover_tpu.obs.stepmarks import LoopWindow, StepMarks, StepsInFlight

    class Scalar:
        def __init__(self, ready):
            self.ready = ready

        def is_ready(self):
            return self.ready

    flight = StepsInFlight(lambda: 0.0)
    flight.dispatched(Scalar(True), {"moe_load_max_over_mean": 1.5})
    flight.dispatched(Scalar(True), {})
    flight.dispatched(Scalar(False), {"moe_load_max_over_mean": 9.0})
    assert flight.poll() == 2
    counted = flight.take_counted()
    assert counted == [{"moe_load_max_over_mean": 1.5}]
    assert flight.take_counted() == []
    window = LoopWindow(first_step=1)
    marks = StepMarks(lambda: 0.0)
    marks.close()
    window.add(marks, 2, 1, counted=counted + [{"moe_load_max_over_mean":
                                                2.5}])
    attrs = window.attrs()
    assert attrs["moe_load_max_over_mean_mean"] == 2.0
    assert attrs["moe_load_max_over_mean_steps"] == 2
    assert "moe_load_max_over_mean_mean" not in LoopWindow(1).attrs()
