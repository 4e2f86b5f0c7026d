"""The gated delta rule (``ops/delta_rule.py``) and its mixer
(``nn.GatedDeltaNet``) at small sizes on the CPU, float32: the chunked WY
form against the recurrence token by token, outputs and every gradient
leaf; the triangular inverse and its own backward rule; the mixer against
the plain reference's (``benchmark/reference/olmo_hybrid.py``); the share
arithmetic of a layer whose heads are dealt over chips; the counters and
the scopes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import timeline
from benchmark.reference import olmo_hybrid as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.ops import delta_rule

CFG = dict(hidden_size=32, linear_num_key_heads=4, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           rms_norm_eps=1e-6)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * scale)


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _apply(module, params, x, training=True):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=training)[0]


def token_by_token(q, k, v, g, beta):
    """The recurrence as its two lines read, a position at a time:
    ``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``."""
    bsz, _, h, dk = q.shape

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        eye = jnp.eye(dk)
        forget = jnp.exp(g_t)[..., None, None] * (
            eye - b_t[..., None, None] * k_t[..., :, None] * k_t[..., None, :])
        state = forget @ state + b_t[..., None, None] \
            * k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((bsz, h, dk, v.shape[-1])),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _case(rng, bsz, length, h=3, dk=8, dv=16, neg=True):
    """What a mixer hands its recurrence: unit q and k (q over sqrt(d_k)),
    decays of 0.2-1.0 a token, beta in (0, 2) or (0, 1)."""
    q, k = (_normal(rng, bsz, length, h, dk) for _ in range(2))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = _normal(rng, bsz, length, h, dv)
    g = -jnp.abs(_normal(rng, bsz, length, h, scale=0.5))
    beta = jax.nn.sigmoid(_normal(rng, bsz, length, h)) * (2.0 if neg else 1.0)
    return q, k, v, g, beta


# ------------------------------------------------------------ the recurrence

@pytest.mark.parametrize("neg", [True, False])
@pytest.mark.parametrize("length,chunk", [(128, 64), (150, 64), (40, 64),
                                          (70, 16)])
def test_the_chunked_form_is_the_recurrence_token_by_token(length, chunk,
                                                           neg):
    """Outputs and every gradient leaf (q, k, v, g, beta) at batch 2, at
    lengths that are and are not whole chunks, shorter than one chunk
    too."""
    case = _case(_rng(length), 2, length, neg=neg)
    seed = _normal(_rng(1), 2, length, 3, 16)

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * seed)

    want = token_by_token(*case)
    got = delta_rule.gated_delta_rule(*case, chunk)
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got, want, tol=2e-5)
    want_g = jax.grad(scalar(token_by_token), argnums=range(5))(*case)
    got_g = jax.grad(scalar(lambda *a: delta_rule.gated_delta_rule(
        *a, chunk)), argnums=range(5))(*case)
    for a, b in zip(got_g, want_g):
        _close(a, b, tol=1e-4)


def test_the_references_recurrence_is_the_same_two_lines():
    """``reference.delta_rule`` (blocks of positions under
    ``jax.checkpoint``) against the recurrence written out here."""
    case = _case(_rng(4), 2, 128)
    _close(reference.delta_rule(*case), token_by_token(*case), tol=1e-5)
    with pytest.raises(ValueError):
        reference.delta_rule(*_case(_rng(4), 1, 100))


def test_a_key_met_again_is_corrected_not_added_twice():
    """The delta term: the same unit key written twice at full strength
    (beta 1, no decay) leaves the SECOND value under it, where plain linear
    attention would read back the sum; chunked and token by token
    alike, also where the repeats fill a chunk (the triangular system at
    its worst: every entry of ``A`` is 1)."""
    k = jnp.zeros((1, 64, 1, 8)).at[..., 0].set(1.0)
    v = _normal(_rng(2), 1, 64, 1, 16)
    g = jnp.zeros((1, 64, 1))
    beta = jnp.ones((1, 64, 1))
    got = delta_rule.gated_delta_rule(k, k, v, g, beta, 64)
    _close(got, v, tol=1e-5)         # o_t = S_t^T k = v_t: the last write
    _close(got, token_by_token(k, k, v, g, beta), tol=1e-5)
    both = delta_rule.gated_delta_rule(k, k, v, g, 2.0 * beta, 64)
    _close(both, token_by_token(k, k, v, g, 2.0 * beta), tol=1e-4)


def test_the_triangular_inverse_and_its_own_backward_rule():
    """``(I + a)^-1`` by block doubling against ``linalg.inv``, at a
    width that is and one that is not a power of two; its ``custom_vjp``
    (``-T^T dT T^T``, strictly lower) against autodiff through the
    inverse."""
    for c in (64, 16, 12):
        a = jnp.tril(_normal(_rng(c), 3, c, c, scale=0.3), -1)
        w = _normal(_rng(1), 3, c, c)
        plain = lambda a: jnp.linalg.inv(jnp.eye(c) + jnp.tril(a, -1))
        _close(delta_rule._unit_lower_inverse(a), plain(a), tol=1e-5)
        got = jax.grad(lambda a: jnp.sum(
            delta_rule._unit_lower_inverse(a) * w))(a)
        _close(got, jax.grad(lambda a: jnp.sum(plain(a) * w))(a), tol=1e-5)
        assert not np.triu(np.asarray(got)).any()


def test_bf16_operands_keep_a_float32_state():
    """Under the training policy q, k and v arrive in bf16: the chunked
    form (operands rounded once a chunk, the state float32) stays within a
    bf16 rounding or two of the float32 recurrence over 512 positions,
    where the recurrence wholly in bf16 (the state rounded after every
    token) is more than twice as far out once the state remembers (a decay
    of 0.96 a token in the mean: a tenth of ``_case``'s). ``o`` comes
    back in bf16."""
    q, k, v, g, beta = _case(_rng(6), 1, 512, h=2, dk=32, dv=32, neg=True)
    g = 0.1 * g
    low = tuple(t.astype(jnp.bfloat16) for t in (q, k, v))
    want = token_by_token(*(t.astype(jnp.float32) for t in low), g, beta)
    got = delta_rule.gated_delta_rule(*low, g, beta, 64)
    assert got.dtype == jnp.bfloat16

    def off(y):
        return float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                     / jnp.linalg.norm(want))

    plain = reference.delta_rule(*low, g.astype(jnp.bfloat16),
                                 beta.astype(jnp.bfloat16))
    assert off(got) < 6e-3
    assert off(plain) > 2 * off(got)


def test_the_counter_says_which_form_ran():
    from bigdl_tpu.telemetry import get_registry, instruments
    fam = instruments(get_registry()).delta_rule_total.labels(form="chunked")
    before = fam.value
    jax.make_jaxpr(lambda *a: delta_rule.gated_delta_rule(*a))(
        *_case(_rng(0), 1, 64))
    assert fam.value == before + 1


# ------------------------------------------------------------------ the mixer

@pytest.fixture(scope="module")
def mixer():
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(7)
    return nn.GatedDeltaNet(32, 4, 8, 16, conv_kernel=4,
                            allow_neg_eigval=True, norm_eps=1e-6,
                            chunk_size=16)


def _named(params):
    return {"in_proj.weight": params["in_proj_weight"],
            "conv.weight": params["conv_weight"], "A_log": params["A_log"],
            "dt_bias": params["dt_bias"],
            "o_norm.weight": params["norm_weight"],
            "o_proj.weight": params["out_proj_weight"]}


@pytest.mark.parametrize("neg", [True, False])
def test_the_mixer_is_the_references_layer(mixer, neg, monkeypatch):
    """Output and every gradient leaf (its six parameters and its input)
    against ``reference.gated_delta_net`` at batch 2 and a length that is
    no whole chunk, with beta doubled and not."""
    monkeypatch.setattr(mixer, "allow_neg_eigval", neg)
    cfg = dict(CFG, linear_allow_neg_eigval=neg)
    u = _normal(_rng(3), 2, 40, 32)
    seed = _normal(_rng(4), 2, 40, 32)
    params = mixer.parameter_tree()
    assert sorted(params) == ["A_log", "conv_weight", "dt_bias",
                              "in_proj_weight", "norm_weight",
                              "out_proj_weight"]
    assert params["in_proj_weight"].shape == (2 * 32 + 2 * 64 + 2 * 4, 32)
    assert params["conv_weight"].shape == (2 * 32 + 64, 4)

    def ours(p, u):
        return jnp.sum(_apply(mixer, p, u) * seed)

    def theirs(p, u):
        return jnp.sum(reference.gated_delta_net(_named(p), "", u, cfg)
                       * seed)

    _close(_apply(mixer, params, u),
           reference.gated_delta_net(_named(params), "", u, cfg), tol=1e-5)
    got = jax.grad(ours, argnums=(0, 1))(params, u)
    want = jax.grad(theirs, argnums=(0, 1))(params, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.asarray(b).any()
        _close(a, b, tol=2e-4)


def test_the_mixer_is_causal_and_starts_from_an_empty_state(mixer):
    """A change at position t moves no output before t; the first
    position's output is what a sequence of one position gives."""
    u = _normal(_rng(5), 1, 24, 32)
    y = mixer.forward(u)
    moved = mixer.forward(u.at[:, 9].add(1.0))
    delta = np.abs(np.asarray(moved - y)).max(-1)[0]
    assert not delta[:9].any() and (delta[9:] > 1e-7).all()
    _close(mixer.forward(u[:, :1]), y[:, :1], tol=1e-5)


def test_the_counters_and_the_scopes_say_what_the_mixer_did(mixer):
    """``bigdl_gated_delta_net_total`` and ``bigdl_delta_rule_total{form=
    chunked}`` count once a trace; the two products land under
    ``delta_proj``, the recurrence (its inverse's own backward rule too)
    under ``delta_rule``, the rest under ``delta_local``."""
    from bigdl_tpu.telemetry import get_registry, instruments
    from bigdl_tpu.telemetry.step_partition import classify, instructions
    ins = instruments(get_registry())
    mixers = ins.gated_delta_net_total.labels()
    rules = ins.delta_rule_total.labels(form="chunked")
    before = mixers.value, rules.value
    u = _normal(_rng(5), 2, 32, 32)
    hlo = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        _apply(mixer, p, u))))).lower(
            mixer.parameter_tree()).compile().as_text()
    assert (mixers.value, rules.value) == (before[0] + 1, before[1] + 1)
    where = {}
    for code, op_name in instructions(hlo).values():
        if "GatedDeltaNet" in op_name or "delta_" in op_name:
            where.setdefault(classify(op_name)[0], set()).add(code)
    assert set(where) == {"delta_proj", "delta_local", "delta_rule"}
    for scope in where:
        assert timeline.scope_instructions(hlo, scope)
    assert "dot" in where["delta_proj"] and "dot" in where["delta_rule"]
    assert "dot" not in where["delta_local"]
    assert "while" in where["delta_rule"]       # the carry over chunk states


def test_the_shares_of_the_heads_add_up_to_the_uncut_layer():
    """The cut of ``configs/olmo-hybrid-7b.json`` tied to the model: a
    layer of 4 heads dealt over two chips, heads 0-1 and 2-3. Each chip's
    ``nn.GatedDeltaNet`` holds its heads' rows of the six in-projections
    and of the three convolutions, its heads' ``A_log`` and ``dt_bias``,
    the shared (d_v,) norm weight and its heads' columns of the
    out-projection; the two outputs ADD UP to what the uncut reference
    gives for the whole layer (nothing in the mixer spans heads: the sum is
    the out-projection's, which a deployment all-reduces)."""
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(11)
    h, dk, dv, e = 4, 8, 16, 32
    whole = nn.GatedDeltaNet(e, h, dk, dv, allow_neg_eigval=True)
    p = whole.parameter_tree()
    u = _normal(_rng(9), 2, 48, e)
    want = reference.gated_delta_net(_named(p), "", u, CFG)

    def rows(first, width):     # a group's rows of the heads first, first+1
        return np.arange(first * width, (first + 2) * width)

    total = 0.0
    for first in (0, 2):
        groups = [(0, dk), (h * dk, dk), (2 * h * dk, dv),
                  (2 * h * dk + h * dv, dv),
                  (2 * h * dk + 2 * h * dv, 1),
                  (2 * h * dk + 2 * h * dv + h, 1)]
        take = np.concatenate([start + rows(first, width)
                               for start, width in groups])
        share = nn.GatedDeltaNet(e, 2, dk, dv, allow_neg_eigval=True)
        mine = dict(
            in_proj_weight=p["in_proj_weight"][take],
            conv_weight=p["conv_weight"][take[:2 * (2 * dk + dv)]],
            A_log=p["A_log"][first:first + 2],
            dt_bias=p["dt_bias"][first:first + 2],
            norm_weight=p["norm_weight"],
            out_proj_weight=p["out_proj_weight"][:, rows(first, dv)])
        assert {k: v.shape for k, v in mine.items()} == {
            k: v.shape for k, v in share.parameter_tree().items()}
        total = total + _apply(share, mine, u)
    _close(total, want, tol=1e-5)


def test_the_pattern_decoder_builds_the_kind_d_and_output_norm_blocks():
    """Kind ``D`` from the ``delta`` group; with ``post_norm`` and
    ``pre_norm=False`` a block holds ONE norm, on its mixer's output:
    ``x + norm_post(mixer(x))``; a block with neither norm is refused."""
    dec = nn.HybridDecoder("D-", 32, delta=dict(
        num_heads=2, key_head_dim=8, value_head_dim=16), mlp=dict(
            hidden_size=48), post_norm=True, pre_norm=False, norm_eps=1e-6)
    assert "D" in nn.HybridDecoder.KINDS
    assert isinstance(dec.layer0.mixer, nn.GatedDeltaNet)
    for block in (dec.layer0, dec.layer1):
        assert sorted(block._modules) == ["mixer", "norm_post"]
        x = _normal(_rng(1), 2, 20, 32)
        _close(block.forward(x), x + block.norm_post.forward(
            block.mixer.forward(x)), tol=1e-6)
    both = nn.HybridDecoder("D", 32, delta=dict(
        num_heads=2, key_head_dim=8, value_head_dim=16), post_norm=True)
    assert sorted(both.layer0._modules) == ["mixer", "norm", "norm_post"]
    with pytest.raises(ValueError):
        nn.HybridBlock(32, dec.layer0.mixer, 1e-6, post_norm=False,
                       pre_norm=False)
