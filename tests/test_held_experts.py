"""`parallel/moe.py:HeldExpertsLayer` by chunks of its sorted rows: the
layer against the benchmark's plain float32 reference (`benchmarks/models/
keye_reference.py:experts`, which multiplies every held expert on every
token under a 0/1 mask) in its output and in the gradient of every leaf and
of the input, under routings planted through the router's weights so that
the held rows end on, before, after and across a chunk's edge; and the
traced program read for what the change is for: no grouped product takes
tokens x top_k rows.

Sizes: 1,024 tokens x 2 experts a token = 2,048 assignments, 2 of 8 experts
held, so a chunk is half the even share's 512 rows and there are 8 chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from dlrover_tpu.parallel.moe import (
    HeldExpertsConfig,
    HeldExpertsLayer,
    chunk_rows,
    held_assignments,
    route_top_k,
)

TOKENS, HIDDEN, WIDTH, EXPERTS, TOP_K = 1024, 16, 8, 8, 2
FIRST, HELD = 2, 2                     # held: experts 2 and 3
ROWS = 256                             # of one chunk, at these sizes
ABSENT = (0, 1)

# name -> ((first choice, second choice, how many tokens), ...); the tokens
# left over go to the absent pair
ROUTINGS = {
    "all_held": ((2, 3, TOKENS),),
    "none_held": (),
    "held_rows_a_chunk": ((2, 3, ROWS // 2),),
    "held_rows_a_chunk_less_one": ((2, 3, ROWS // 2 - 1), (2, 0, 1)),
    "held_rows_a_chunk_and_one": ((2, 3, ROWS // 2), (0, 3, 1)),
    # expert 2 over the first edge, expert 3 over the second and the third
    "an_expert_across_the_edge": ((2, 0, 300), (1, 3, 400)),
}
HELD_ROWS = {"all_held": (TOKENS, TOKENS), "none_held": (0, 0),
             "held_rows_a_chunk": (ROWS // 2, ROWS // 2),
             "held_rows_a_chunk_less_one": (ROWS // 2, ROWS // 2 - 1),
             "held_rows_a_chunk_and_one": (ROWS // 2, ROWS // 2 + 1),
             "an_expert_across_the_edge": (300, 400)}


def _config(held=HELD, first=FIRST) -> HeldExpertsConfig:
    return HeldExpertsConfig(
        num_experts=EXPERTS, experts_held=held, first_expert=first,
        top_k=TOP_K, hidden_size=HIDDEN, expert_intermediate=WIDTH)


def _planted(routing: str, held=HELD, first=FIRST):
    """(params, x): seeded leaves and tokens; for a planted routing the
    first `EXPERTS` features of a token are its class's one-hot and the
    router sends each class to its pair, 20 and 19 above the rest."""
    rng = np.random.default_rng(39)
    x = rng.normal(size=(2, TOKENS // 2, HIDDEN)).astype(np.float32)
    params = {
        "router": rng.normal(scale=0.3, size=(HIDDEN, EXPERTS)),
        "w1": rng.normal(scale=0.4, size=(held, HIDDEN, WIDTH)),
        "w3": rng.normal(scale=0.4, size=(held, HIDDEN, WIDTH)),
        "w2": rng.normal(scale=0.4, size=(held, WIDTH, HIDDEN))}
    if routing != "as_it_falls":
        pairs = ROUTINGS[routing]
        pairs += (ABSENT + (TOKENS - sum(n for _, _, n in pairs),),)
        flat = x.reshape(TOKENS, HIDDEN)
        flat[:, :EXPERTS] = 0.0
        params["router"][:EXPERTS] = 0.0
        # classes interleaved over the tokens, not in blocks
        of_token = rng.permutation(np.repeat(
            np.arange(len(pairs)), [n for _, _, n in pairs]))
        flat[np.arange(TOKENS), of_token] = 1.0
        for c, (one, two, _) in enumerate(pairs):
            params["router"][c, one] = 20.0
            params["router"][c, two] = 19.0
    return ({name: jnp.asarray(leaf, jnp.float32)
             for name, leaf in params.items()}, jnp.asarray(x))


def _both(cfg: HeldExpertsConfig, params, x):
    """((output, gradients) of the layer, the same of the reference), the
    gradients those of one seeded linear function of the output, with
    respect to every leaf and to the input."""
    plain = harness.load_module(harness.MODELS, "keye_reference")
    weigh = jnp.asarray(np.random.default_rng(7).normal(size=x.shape),
                        jnp.float32)
    share = {"num_local_experts": cfg.experts_held,
             "first_expert": cfg.first_expert,
             "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True}

    def mine(p, z):
        out = HeldExpertsLayer(cfg).apply({"params": p}, z,
                                          mutable=["counters"])[0]
        return jnp.sum(out * weigh), out

    def theirs(p, z):
        out = plain.experts(z, {"moe/" + k: v for k, v in p.items()}, share,
                            "f32")
        return jnp.sum(out * weigh), out

    return tuple(
        (out, grads) for (_, out), grads in (
            jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
                params, x) for f in (mine, theirs)))


def _agree(mine, theirs):
    """To float32 rounding at the scale of what is compared: sums of up
    to 1,024 rows in another order (the parent's layer reads 2.5e-6 of the
    scale at worst on these cases; a dropped row reads 1e-3 and more)."""
    (out, (leaves, d_x)), (ref_out, (ref_leaves, ref_d_x)) = mine, theirs
    compared = [(out, ref_out), (d_x, ref_d_x)] + [
        (leaves[name], ref_leaves[name])
        for name in ("router", "w1", "w2", "w3")]
    for got, ref in compared:
        assert bool(jnp.all(jnp.isfinite(got)))
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(got, ref, rtol=0, atol=5e-6 * scale)


@pytest.mark.parametrize("routing", ["as_it_falls"] + sorted(ROUTINGS))
def test_every_held_row_under_every_routing(routing):
    """Output and every gradient against the reference; the planted routing
    is the one the case names (each held expert's rows counted)."""
    cfg = _config()
    assert chunk_rows(TOKENS * TOP_K, HELD, EXPERTS) == ROWS
    params, x = _planted(routing)
    mine, theirs = _both(cfg, params, x)
    _agree(mine, theirs)
    _, experts = route_top_k(
        jnp.dot(x.reshape(-1, HIDDEN), params["router"],
                precision="highest"), TOP_K, True)
    _, _, sizes = held_assignments(experts, FIRST, HELD)
    sown = HeldExpertsLayer(cfg).apply({"params": params}, x,
                                       mutable=["counters"])[1]["counters"]
    run = float(sown["moe_chunks_run"][0])
    assert run == -(-int(jnp.sum(sizes)) // ROWS)
    if routing == "as_it_falls":
        assert 0 < int(jnp.min(sizes)) and 2 <= run <= 3
        return
    assert tuple(int(n) for n in sizes) == HELD_ROWS[routing]
    out, (leaves, d_x) = mine
    if routing == "all_held":
        # the worst case is still computed, not dropped: every chunk runs
        assert run == 8 and float(jnp.min(jnp.max(jnp.abs(out), -1))) > 0
    if routing == "none_held":
        # no chunk runs: zeros out, zero gradients, no NaN
        assert run == 0
        for leaf in (out, d_x, *leaves.values()):
            assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_every_expert_held_is_one_chunk():
    """`experts_held == num_experts`: the chunk is the whole sorted order."""
    cfg = _config(held=EXPERTS, first=0)
    assert chunk_rows(TOKENS * TOP_K, EXPERTS, EXPERTS) == TOKENS * TOP_K
    params, x = _planted("as_it_falls", held=EXPERTS, first=0)
    _agree(*_both(cfg, params, x))
    sown = HeldExpertsLayer(cfg).apply({"params": params}, x,
                                       mutable=["counters"])[1]["counters"]
    assert float(sown["moe_chunks_run"][0]) == 1.0
    assert float(sown["moe_held_rows_share"][0]) == 1.0


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (scan and cond bodies, custom rules)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_no_grouped_product_takes_every_assignments_rows():
    """tokens x top_k is 8 chunks here (1 of 8 experts held): forward and
    backward, every grouped product's operands have one chunk's rows and
    none has tokens x top_k rows of `hidden` columns."""
    cfg = _config(held=1, first=3)
    assignments = TOKENS * TOP_K
    rows = chunk_rows(assignments, 1, EXPERTS)
    assert assignments == 8 * rows
    params, x = _planted("as_it_falls", held=1, first=3)

    def loss(p, z):
        return jnp.sum(HeldExpertsLayer(cfg).apply(
            {"params": p}, z, mutable=["counters"])[0] ** 2)

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    grouped = [eqn for eqn in _equations(traced.jaxpr)
               if eqn.primitive.name.startswith("ragged_dot")]
    # three products forward; backward three again (the chunk's forward is
    # formed anew there) and two transposes each
    assert len(grouped) >= 9
    for eqn in grouped:
        for operand in eqn.invars:
            shape = operand.aval.shape
            assert shape[0] != assignments, (eqn.primitive.name, shape)
            if len(shape) == 2 and shape[-1] in (HIDDEN, WIDTH):
                assert shape[0] == rows, (eqn.primitive.name, shape)


def test_the_chunk_counter_reaches_the_window_and_the_log(caplog):
    """`moe_chunks_run`, sown beside the layer's two load counters, goes
    the way they go: the step's metrics (mean over the layers), the steps
    seen done, the `train_window` span's attrs and the worker's log."""
    import logging

    import optax

    from dlrover_tpu.models.keye import Keye, KeyeConfig
    from dlrover_tpu.models.llama import cross_entropy_loss
    from dlrover_tpu.obs.stepmarks import LoopWindow, StepMarks, StepsInFlight
    from dlrover_tpu.parallel.mesh import MeshSpec, create_mesh
    from dlrover_tpu.trainer.elastic_loop import ElasticTrainLoop
    from dlrover_tpu.trainer.train_step import build_trainer

    cfg = KeyeConfig.tiny(dtype=jnp.float32, norm_impl="reference",
                          embed_impl="gather")
    tokens = np.random.default_rng(3).integers(0, 256, (2, 64),
                                               dtype=np.int32)
    trainer = build_trainer(
        Keye(cfg), optax.sgd(0.1),
        create_mesh(MeshSpec(), jax.devices("cpu")[:1]),
        jnp.zeros((2, 64), jnp.int32), cross_entropy_loss, micro_batch=2)
    _, metrics = trainer.step(
        trainer.init(jax.random.PRNGKey(0)),
        *trainer.shard_batch(tokens, np.roll(tokens, -1, axis=-1)))
    # 256 assignments, half of the experts held: one chunk of all of them
    assert float(metrics["moe_chunks_run"]) == 1.0
    flight = StepsInFlight(lambda: 0.0)
    flight.dispatched(metrics["loss"], {
        name: value for name, value in metrics.items()
        if name not in ("loss", "grad_norm")})
    jax.block_until_ready(metrics)
    assert flight.poll() == 1
    window = LoopWindow(first_step=1)
    marks = StepMarks(lambda: 0.0)
    marks.close()
    window.add(marks, 1, 0, counted=flight.take_counted())
    window.wall_s = 1.0
    attrs = window.attrs()
    assert attrs["moe_chunks_run_mean"] == 1.0
    assert attrs["moe_chunks_run_steps"] == 1
    assert 0.0 < attrs["moe_held_rows_share_mean"] < 1.0
    # the package's logger hands nothing up to the root's handlers
    logger = logging.getLogger("dlrover_tpu")
    logger.addHandler(caplog.handler)
    try:
        ElasticTrainLoop._emit_train_window(window)
    finally:
        logger.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records
            if "the model's counters" in r.getMessage()]
    assert len(said) == 1 and "moe_chunks_run=1 (1)" in said[0]
    assert "moe_held_rows_share=" in said[0]
    assert "moe_load_max_over_mean=" in said[0]
