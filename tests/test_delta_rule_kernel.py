"""The gated delta rule's kernel form (``ops/delta_rule.py``:
``delta_rule_fwd`` / ``delta_rule_bwd``) in Pallas' interpreter on the CPU:
the forward against the XLA form and the recurrence token by token, every
cotangent against ``jax.vjp`` of the XLA form, the padded tail, the carried
state's reset between (batch, head) pairs, the triangular system at its
worst, the precision contract, the path rule, the counter, the controls'
seam and the calls' names and scopes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import olmo_hybrid as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.ops import delta_rule
from bigdl_tpu.telemetry import step_partition as sp
from bigdl_tpu.utils.rng import manual_seed

from test_gated_delta_net import _case, _close, _normal, _rng, token_by_token

BF16, F32 = jnp.bfloat16, jnp.float32
LEAVES = ("q", "k", "v", "g", "beta")


def kernel_form(*case, chunk=64):
    return delta_rule._delta_kernel(*case, chunk, interpret=True)


def xla_form(*case, chunk=64):
    return delta_rule._delta_chunked(*case, chunk)


def _out_and_cotangents(form, case, seed):
    """``form``'s output and its five cotangents from ``seed``, as one
    program (XLA's CPU backend runs the XLA form's bf16 transposes only
    compiled)."""
    def run(*args):
        out, back = jax.vjp(form, *args[:-1])
        return (out,) + back(args[-1])
    return jax.jit(run)(*case, seed)


def _low(case):
    """q, k and v as the training policy hands them; g and beta float32."""
    return tuple(t.astype(BF16) for t in case[:3]) + tuple(case[3:])


def _on_the_kernel_path(monkeypatch):
    monkeypatch.setattr(delta_rule, "takes_kernel", lambda *a: True)


def _form_counts():
    from bigdl_tpu.telemetry import get_registry, instruments
    fam = instruments(get_registry()).delta_rule_total
    return {f: fam.labels(form=f).value for f in ("kernel", "chunked")}


# ---------------------------------------------------------------- the forward

@pytest.mark.parametrize("neg", [True, False])
@pytest.mark.parametrize("bsz,length,h,dk,dv", [
    (2, 128, 3, 32, 64),        # two chunks, one grid cell a (batch, head)
    (1, 1024, 2, 32, 64),       # sixteen chunks: two grid cells along a head
    (1, 192, 1, 128, 128),      # a head of whole lane tiles, three chunks
])
def test_the_forward_call_is_the_xla_form_and_the_recurrence(
        bsz, length, h, dk, dv, neg):
    """Float32 operands, so both comparisons are tight; beta in (0, 2) (the
    state's transition with eigenvalues in (-1, 1), ``allow_neg_eigval``)
    and in (0, 1)."""
    case = _case(_rng(length), bsz, length, h=h, dk=dk, dv=dv, neg=neg)
    got = kernel_form(*case)
    assert got.shape == (bsz, length, h, dv) and got.dtype == F32
    _close(got, xla_form(*case), tol=2e-5)
    _close(got, token_by_token(*case), tol=5e-5)


# -------------------------------------------------------------- the backward

@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def cotangents(request):
    """Every cotangent of a (2, 200, 2 heads of 32 / 64) case, kernel form
    and ``jax.vjp`` of the XLA form, from one seed of the output's shape;
    a length that is no whole chunk, four chunks over two grid cells'
    worth of state."""
    low = request.param == "bfloat16"
    case = _case(_rng(7), 2, 200, h=2, dk=32, dv=64)
    seed = _normal(_rng(8), 2, 200, 2, 64)
    if low:
        case, seed = _low(case), seed.astype(BF16)
    return (low, dict(zip(LEAVES, _out_and_cotangents(kernel_form, case,
                                                      seed)[1:])),
            dict(zip(LEAVES, _out_and_cotangents(xla_form, case, seed)[1:])))


@pytest.mark.parametrize("leaf", LEAVES)
def test_each_cotangent_is_autodiffs_of_the_xla_form(cotangents, leaf):
    """``dq``, ``dk``, ``dv``, ``dg`` and ``dbeta`` of ``delta_rule_bwd``
    (and the XLA epilogue that un-cumulates ``d gc``): float32 operands to
    float32's rounding, bf16 operands to a few bf16 roundings (the two
    forms round a cotangent where it enters a product, and carry the state
    through ``V'`` and through ``M`` respectively)."""
    low, got, want = cotangents
    assert got[leaf].dtype == want[leaf].dtype
    assert got[leaf].shape == want[leaf].shape
    assert np.asarray(want[leaf], np.float32).any()
    _close(got[leaf].astype(F32), want[leaf].astype(F32),
           tol=3e-2 if low else 1e-4)


def test_the_padded_tail_writes_nothing_and_decays_nothing():
    """A length of 150 is padded to three chunks with ``g = 0`` and ``beta
    = 0``: the first 150 outputs are those of the first 150 positions of a
    192-long sequence that goes on (the tail changes no state that a real
    position reads), and the cotangents of the real positions are those of
    the XLA form, which pads the same way."""
    long = _case(_rng(3), 1, 192, h=2, dk=32, dv=64)
    short = tuple(t[:, :150] for t in long)
    _close(kernel_form(*short), kernel_form(*long)[:, :150], tol=1e-6)
    _close(kernel_form(*short), token_by_token(*short), tol=5e-5)
    seed = _normal(_rng(4), 1, 150, 2, 64)
    got = jax.vjp(kernel_form, *short)[1](seed)
    want = jax.vjp(xla_form, *short)[1](seed)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a, b, tol=1e-4)


def test_the_state_does_not_leak_from_one_batch_row_or_head_to_the_next():
    """The carried state lives in VMEM scratch along the grid's last axis
    and starts from zero for every (batch, head): each pair's output and
    cotangents are what that pair gives alone."""
    case = _case(_rng(5), 2, 128, h=3, dk=32, dv=64)
    seed = _normal(_rng(6), 2, 128, 3, 64)
    out, back = jax.vjp(kernel_form, *case)
    grads = back(seed)
    for b, h in ((0, 0), (1, 0), (0, 2), (1, 2)):
        alone = tuple(t[b:b + 1, :, h:h + 1] for t in case)
        mine, mine_back = jax.vjp(kernel_form, *alone)
        _close(out[b:b + 1, :, h:h + 1], mine, tol=1e-6)
        for a, want in zip(grads, mine_back(seed[b:b + 1, :, h:h + 1])):
            _close(a[b:b + 1, :, h:h + 1], want, tol=1e-5)


def test_a_key_met_again_inside_a_chunk_is_corrected_not_added_twice():
    """The case ``test_gated_delta_net`` holds the XLA form to: one unit
    key written 64 times at full strength and no decay (every entry of
    ``A`` is 1, the substitution at its worst) leaves the LAST value under
    it; at beta 2 the kernel is the recurrence too."""
    k = jnp.zeros((1, 64, 1, 32)).at[..., 0].set(1.0)
    v = _normal(_rng(2), 1, 64, 1, 64)
    g = jnp.zeros((1, 64, 1))
    beta = jnp.ones((1, 64, 1))
    _close(kernel_form(k, k, v, g, beta), v, tol=1e-5)
    _close(kernel_form(k, k, v, g, 2.0 * beta),
           token_by_token(k, k, v, g, 2.0 * beta), tol=1e-4)


def test_bf16_operands_keep_a_float32_state():
    """As ``test_gated_delta_net`` holds the XLA form: over 512 positions
    with a state that remembers, the kernel form (operands rounded once a
    chunk, state and ``T`` float32) stays within a bf16 rounding or two of
    the float32 recurrence, where the recurrence wholly in bf16 is more
    than twice as far out; ``o`` comes back in bf16 and is the XLA form's
    to a rounding."""
    q, k, v, g, beta = _case(_rng(6), 1, 512, h=2, dk=32, dv=64, neg=True)
    g = 0.1 * g
    low = _low((q, k, v, g, beta))
    want = token_by_token(*(t.astype(F32) for t in low[:3]), g, beta)
    got = kernel_form(*low)
    assert got.dtype == BF16

    def off(y):
        return float(jnp.linalg.norm(y.astype(F32) - want)
                     / jnp.linalg.norm(want))

    plain = reference.delta_rule(*low[:3], g.astype(BF16), beta.astype(BF16))
    assert off(got) < 6e-3
    assert off(plain) > 2 * off(got)
    _close(got.astype(F32), xla_form(*low).astype(F32), tol=2e-2)


def test_the_cells_own_head_shape():
    """15 heads of 96 / 192 at chunk 64, bf16 operands, two chunks: the
    shape the Olmo-Hybrid cell runs, at a short length; forward and the
    worst cotangent against the XLA form."""
    case = _low(_case(_rng(9), 1, 128, h=15, dk=96, dv=192))
    seed = _normal(_rng(10), 1, 128, 15, 192).astype(BF16)
    want = _out_and_cotangents(xla_form, case, seed)
    got = _out_and_cotangents(kernel_form, case, seed)
    assert got[0].shape == (1, 128, 15, 192) and got[0].dtype == BF16
    for a, b in zip(got, want):
        _close(a.astype(F32), b.astype(F32), tol=3e-2)


# ------------------------------------------ the path rule, counter and seam

@pytest.mark.parametrize("args,kernel", [
    (("tpu", BF16, F32, 64, 96, 192), True),        # the cell, 15 or 30 heads
    (("tpu", BF16, F32, 64, 128, 128), True),       # the usual published head
    (("cpu", BF16, F32, 64, 96, 192), False),
    (("gpu", BF16, F32, 64, 96, 192), False),
    (("tpu", F32, F32, 64, 96, 192), False),        # float32 operands
    (("tpu", jnp.float16, F32, 64, 96, 192), False),
    (("tpu", BF16, BF16, 64, 96, 192), False),      # a bf16 gate
    (("tpu", BF16, F32, 16, 8, 16), False),         # tier-1's mixer
    (("tpu", BF16, F32, 64, 8, 16), False),         # tier-1's recurrence
    (("tpu", BF16, F32, 128, 96, 192), False),      # another chunk
    (("tpu", BF16, F32, 64, 100, 192), False),      # no packed sublane tile
    (("tpu", BF16, F32, 64, 256, 192), False),      # keys past a lane tile
    (("tpu", BF16, F32, 64, 96, 200), False),       # values Mosaic cuts
    (("tpu", BF16, F32, 64, 96, 512), False),
])
def test_which_recurrences_take_the_kernels(args, kernel):
    """The path rule as its docstring states it: backend, the operands'
    dtype and the gate's, chunk, head sizes. The number of heads is not in
    it: 15 held or all 30 are grid cells."""
    assert delta_rule.takes_kernel(*args) is kernel


def test_the_counter_says_which_form_was_traced(monkeypatch):
    """``bigdl_delta_rule_total{form}`` at trace time: ``chunked`` on this
    CPU, ``kernel`` where the rule admits the call (a TPU backend stood in:
    tracing lowers nothing), ``chunked`` again for float32 operands
    there."""
    case = _low(_case(_rng(0), 1, 128, h=2, dk=96, dv=192))
    # a function of its own a trace: jax keeps the traces of one
    trace = lambda *a: jax.make_jaxpr(
        lambda *b: delta_rule.gated_delta_rule(*b))(*a)
    before = _form_counts()
    trace(*case)
    assert _form_counts() == dict(before, chunked=before["chunked"] + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace(*case)
    trace(*(t.astype(F32) for t in case))
    assert _form_counts() == {"kernel": before["kernel"] + 1,
                              "chunked": before["chunked"] + 2}


def test_a_replaced_wy_is_run_the_controls_seam(monkeypatch):
    """``benchmark/builders/olmo_hybrid.planted`` plants ``no_delta_term``
    by replacing ``ops.delta_rule._wy`` BY NAME (``W = 0``, ``U = beta
    v``). Only the XLA form calls it, so while it is replaced the
    recurrence goes through that form wherever it runs: the replacement is
    run, the numbers move, the count says ``chunked``; put back, the kernel
    form and the sound numbers are back."""
    _on_the_kernel_path(monkeypatch)
    case = _case(_rng(2), 1, 128, h=2, dk=32, dv=64)
    before = _form_counts()
    sound = delta_rule.gated_delta_rule(*case)
    assert _form_counts() == dict(before, kernel=before["kernel"] + 1)
    _close(sound, token_by_token(*case), tol=5e-5)
    ran = []

    def no_delta_term(k, v, gc, beta, decay):
        ran.append(decay.shape)
        return (jnp.zeros(k.shape, F32), v.astype(F32) * beta[..., None])

    with monkeypatch.context() as planted:
        planted.setattr(delta_rule, "_wy", no_delta_term)
        moved = delta_rule.gated_delta_rule(*case)
    assert ran == [(1, 2, 2, 64, 64)]
    assert _form_counts() == {"kernel": before["kernel"] + 1,
                              "chunked": before["chunked"] + 1}
    assert float(jnp.abs(moved - sound).max()) > 1e-2
    again = delta_rule.gated_delta_rule(*case)
    assert _form_counts()["kernel"] == before["kernel"] + 2
    _close(again, sound, tol=1e-7)


# -------------------------------------------------- the mixer over the kernels

def _mixer():
    manual_seed(7)
    return nn.GatedDeltaNet(32, 2, 32, 64, conv_kernel=4,
                            allow_neg_eigval=True, norm_eps=1e-6,
                            chunk_size=64)


def _apply(module, params, x):
    return functional_apply(module, params, module.buffer_tree(), x,
                            training=True)[0]


def test_the_mixer_over_the_kernels_is_the_mixer_over_the_xla_form(
        monkeypatch):
    """``nn.GatedDeltaNet`` chooses nothing: the same module, output and
    every parameter's gradient, with the recurrence in either form."""
    m = _mixer()
    params = m.parameter_tree()
    u = _normal(_rng(3), 2, 100, 32)
    seed = _normal(_rng(4), 2, 100, 32)
    loss = lambda p, u: jnp.sum(_apply(m, p, u) * seed)
    want = _apply(m, params, u)
    want_g = jax.grad(loss, argnums=(0, 1))(params, u)
    before = _form_counts()
    _on_the_kernel_path(monkeypatch)
    _close(_apply(m, params, u), want, tol=1e-5)
    got_g = jax.grad(loss, argnums=(0, 1))(params, u)
    assert _form_counts()["kernel"] == before["kernel"] + 2
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        _close(a, b, tol=2e-4)


def test_the_calls_names_and_scopes(monkeypatch):
    """The two calls carry their names and the scope ``delta_rule``, the
    forward's in the forward and the backward's (a ``custom_vjp`` rule
    enters the scope by hand) in the backward, so the step's partition and
    the readers that select by scope find them; the transposes that take
    the head out of the token's lanes and the un-cumulation of ``d gc``
    are the layer's too."""
    m = _mixer()
    params = m.parameter_tree()
    u = _normal(_rng(1), 1, 128, 32)
    _on_the_kernel_path(monkeypatch)
    hlo = jax.jit(jax.grad(lambda p, u: jnp.sum(_apply(m, p, u)),
                           argnums=(0, 1))).lower(
                               params, u).compile().as_text()
    names = [op for _, op in sp.instructions(hlo).values()]
    for call, pas in (("delta_rule_fwd", "forward"),
                      ("delta_rule_bwd", "backward")):
        mine = [op for op in names if f"/{call}/" in op]
        assert mine, call
        assert {sp.classify(op) for op in mine} == {("delta_rule", pas)}, call
    assert not [op for op in names if "delta_rule/delta_rule/" in op]
    of_rule = {sp.classify(op)[1] for op in names
               if sp.classify(op)[0] == "delta_rule"}
    assert of_rule == {"forward", "backward"}
