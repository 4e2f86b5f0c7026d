"""The held expert layer's routing tables (``MoE._held_forward``: ``tok``,
``gate``, ``counts``) against a plain numpy reference (stable argsort,
bincount, fancy indexing), for the routing forms of the benchmark's five
expert cells at a tiny size; the gradient that reaches the router through
them against the gather form's; and what the routing scope of each cell's
tiny model holds: no per-pick gather or scatter, one sort a block."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import MoE, token_ids

D, E, T, VOCAB = 16, 12, 40, 23

#: the routing forms of the five cells (N, T, J, S, F), widths apart
FORMS = {
    "top_k_bias_trained": dict(k=3, route_scale=2.5),
    "top_k_untrained": dict(k=4, route_scale=2.826, train_router=False,
                            shared_hidden=8, activation="swiglu"),
    "table_sigmoid": dict(k=4, route_scale=2.5, train_router=False,
                          pick_rows=VOCAB, activation="swiglu"),
    "table_softmax_picked_given": dict(
        k=3, train_router=False, pick_rows=VOCAB, activation="reglu",
        score="softmax_picked", router_input="given"),
    "table_sigmoid_eps": dict(k=2, train_router=False, pick_rows=VOCAB,
                              activation="swiglu", renorm_eps=1e-6),
}
#: which experts are here: a run of ids, ids as a placement by load leaves
#: them, every expert, fewer than a token picks (``rows = t * n``)
HELD = {"contiguous": (2, 3, 4, 5, 6), "scattered": (9, 0, 7, 3, 11),
        "all": None, "fewer_than_k": (5,)}


def _layer(form, held, draw="random", seed=0, **over):
    rng = np.random.default_rng(seed)
    kw = dict(FORMS[form], **over)
    moe = MoE(D, 8, E, dispatch="held", bias=False, held=held, **kw)
    moe.gate_weight = jnp.asarray(
        rng.standard_normal((D, E)).astype(np.float32))
    here = np.asarray(moe.held)
    away = np.setdiff1d(np.arange(E), here)
    want = {"random": np.arange(E), "none_land": away, "all_land": here}[draw]
    if moe.pick_rows:
        moe.pick_table = jnp.asarray(np.stack(
            [rng.permutation(want)[:moe.k] for _ in range(VOCAB)]
        ).astype(np.float32))
    else:
        bias = rng.standard_normal(E).astype(np.float32)
        if draw != "random":
            bias[want] += 100.0
        moe.select_bias = jnp.asarray(bias)
    x = jnp.asarray(rng.standard_normal((2, T // 2, D)).astype(np.float32))
    r = jnp.asarray(rng.standard_normal((2, T // 2, D)).astype(np.float32))
    ids = jnp.asarray(rng.integers(1, VOCAB + 1, (2, T // 2)))
    return moe, x, r, ids


def _weights(moe, w):
    """The combine weights of the picked scores ``w`` (T, k), in the
    layer's own arithmetic."""
    if moe.score == "sigmoid":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + moe.renorm_eps)
    else:
        w = jax.nn.softmax(w, axis=-1)
    return w * moe.route_scale


def _scores(moe, stream):
    scores = jnp.dot(stream.reshape(-1, D), moe.gate_weight,
                     preferred_element_type=jnp.float32)
    return jax.nn.sigmoid(scores) if moe.score == "sigmoid" else scores


def _reference(moe, stream, ids):
    """(tok, gate, counts) by numpy: fancy indexing, a stable argsort, a
    bincount."""
    scores = np.asarray(_scores(moe, stream))
    t, k, n = scores.shape[0], moe.k, len(moe.held)
    if moe.pick_rows:
        picked = np.asarray(moe.pick_table)[
            np.asarray(ids).reshape(-1) - 1].astype(np.int64)
    else:
        picked = np.argsort(-(scores + np.asarray(moe.select_bias)),
                            axis=-1, kind="stable")[:, :k]
    weight = np.asarray(_weights(
        moe, jnp.asarray(scores[np.arange(t)[:, None], picked])))
    local_of = np.full((E,), n, np.int64)
    local_of[list(moe.held)] = np.arange(n)
    lid = local_of[picked.reshape(-1)]
    order = np.argsort(lid, kind="stable")[:t * min(k, n)]
    return (order // k, weight.reshape(-1)[order],
            np.bincount(lid, minlength=n + 1)[:n])


def _run(moe, x, r, ids):
    with token_ids(ids):
        return moe.forward((x, r) if moe.router_input == "given" else x)


@pytest.fixture
def tables(monkeypatch):
    """What the layer hands its grouped product, in call order."""
    seen = []

    def grouped_rows(hidden, activate, weights, x, tok, gate, counts):
        seen.append((tok, gate, counts))
        return jnp.zeros(x.shape, jnp.float32)

    monkeypatch.setattr(expert, "_grouped_rows", grouped_rows)
    return seen


CASES = [(form, held, "random") for form in FORMS for held in HELD] \
    + [(form, "contiguous", draw) for form in FORMS
       for draw in ("none_land", "all_land")]


@pytest.mark.parametrize("form,held,draw", CASES)
def test_the_tables_are_the_numpy_reference_s(form, held, draw, tables):
    moe, x, r, ids = _layer(form, HELD[held], draw)
    _run(moe, x, r, ids)
    (tok, gate, counts), = tables
    want_tok, want_gate, want_counts = _reference(
        moe, r if moe.router_input == "given" else x, ids)
    n, k = len(moe.held), moe.k
    assert tok.dtype == jnp.int32 and counts.dtype == jnp.int32 \
        and gate.dtype == jnp.float32
    assert tok.shape == gate.shape == (T * min(k, n),)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    np.testing.assert_array_equal(np.asarray(tok), want_tok)
    np.testing.assert_array_max_ulp(np.asarray(gate), want_gate, maxulp=1)
    landed = {"none_land": 0, "all_land": T * k}.get(draw)
    if landed is not None:
        assert int(want_counts.sum()) == landed


def _gather_form(moe, stream, ids):
    """The tables by a gather of the picked scores, a lookup of the local
    ids, an argsort and a gather of the sorted weights: what the layer did
    before it compared and sorted with payloads, differentiable alike."""
    scores = _scores(moe, stream)
    if moe.pick_rows:
        picked = moe.pick_table[ids.reshape(-1) - 1].astype(jnp.int32)
    else:
        picked = jax.lax.top_k(scores + moe.select_bias, moe.k)[1]
    weight = _weights(moe, jnp.take_along_axis(scores, picked, axis=-1))
    n = len(moe.held)
    local_of = np.full((E,), n, np.int32)
    local_of[list(moe.held)] = np.arange(n, dtype=np.int32)
    lid = jnp.asarray(local_of)[picked.reshape(-1)]
    order = jnp.argsort(lid, stable=True)[:T * min(moe.k, n)]
    return order // moe.k, weight.reshape(-1)[order]


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("held", ["scattered", "fewer_than_k"])
def test_a_trained_router_s_gradient_is_the_gather_form_s(form, held,
                                                         monkeypatch):
    """``train_router=True``: the gradient of a function of the sorted
    weights with respect to the router's matrix and its input, through the
    layer's tables and through the gather form's."""
    moe, x, r, ids = _layer(form, HELD[held], train_router=True,
                            shared_hidden=0)
    given = moe.router_input == "given"
    stream = r if given else x
    c = jnp.asarray(np.random.default_rng(5).standard_normal(
        (T * min(moe.k, len(moe.held)),)).astype(np.float32))

    def grouped_rows(hidden, activate, weights, x, tok, gate, counts):
        return jnp.zeros(x.shape, jnp.float32).at[tok].add(
            (gate * c)[:, None])

    monkeypatch.setattr(expert, "_grouped_rows", grouped_rows)

    def through_the_layer(gate_weight, stream):
        params = dict(moe.parameter_tree(), gate_weight=gate_weight)
        with token_ids(ids):
            y = functional_apply(moe, params, moe.buffer_tree(),
                                 (x, stream) if given else stream)[0]
        return jnp.sum(y[..., 0])

    def through_the_gather_form(gate_weight, stream):
        moe.gate_weight = gate_weight
        tok, gate = _gather_form(moe, stream, ids)
        return jnp.sum(gate * c)

    kept = moe.gate_weight
    got = jax.grad(through_the_layer, argnums=(0, 1))(kept, stream)
    want = jax.grad(through_the_gather_form, argnums=(0, 1))(kept, stream)
    moe.gate_weight = kept
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(w).max()))


# ------------------------------------------- what the routing scope holds

def _under_routing(jaxpr):
    """Primitive counts of the equations traced under ``moe_route`` or
    ``moe_route_ahead``, whatever the pass."""
    from test_block_remat import _eqns
    return collections.Counter(
        eqn.primitive.name for eqn in _eqns(jaxpr)
        if "moe_route" in str(eqn.source_info.name_stack))


#: the cells whose expert layers run ``MoE(dispatch="held")``: (picks from
#: a table, routers trained)
ROUTED = {"nemotron-3-nano-30b-a3b-train-s8192": (False, True),
          "trinity-mini-train-s8192": (False, False),
          "joyai-llm-flash-train-s8192": (True, False),
          "smallthinker-21b-a3b-train-s16384": (True, False),
          "lfm2-24b-a2b-train-s8192": (True, False)}
PER_PICK = ("scatter", "dynamic_slice", "dynamic_update_slice", "cumsum",
            "while")


@pytest.mark.parametrize("cell_name", sorted(ROUTED))
def test_the_routing_scope_holds_one_sort_and_no_per_pick_gather(cell_name):
    """The differentiated step of the cell's rehearsal model, its blocks
    under block remat (the scope is traced once a block: what is kept is
    not traced again)."""
    from test_block_remat import _rehearsal_model, _step_jaxpr
    table, trained = ROUTED[cell_name]
    cell, cfg, model, dec = _rehearsal_model(cell_name)
    layers = [m for m in model.modules() if isinstance(m, MoE)]
    blocks = len(layers)
    assert blocks and all(bool(m.pick_rows) == table
                          and m.train_router == trained for m in layers)
    seen = _under_routing(_step_jaxpr(cell, cfg, model))
    assert seen["dot_general"] >= blocks        # the scope is the router's
    assert seen["sort"] == blocks
    assert seen["top_k"] == (0 if table else blocks)
    # the table's ROW gather by token id is the one gather that stays
    assert seen["gather"] == (blocks if table else 0)
    # a trained router: the sorted weights' way back to pick order is a
    # data-dependent permutation, one scatter-add a block (``_in_order``);
    # the picked scores' transpose is a dense select
    assert seen["scatter-add"] == (blocks if trained else 0)
    assert not [p for p in PER_PICK if seen[p]], seen
