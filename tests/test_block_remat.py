"""Block remat's save-list (``ops/remat.py``): keeping a value changes no
number, every name on the list is honoured in the differentiated step
(the work behind it appears once a block, not twice), the producers' tags
and the list agree, and the trace-time counter says which names a program
met. Small sizes on the CPU; the flash kernels in Pallas' interpreter."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu import nn
from bigdl_tpu.nn import attention, hybrid
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.ops import flash_attention as fa
from bigdl_tpu.ops import remat
from bigdl_tpu.telemetry import get_registry, instruments

E = 32
KW = dict(
    mamba=dict(num_heads=4, head_dim=8, state_size=8, n_groups=2,
               chunk_size=8),
    moe=dict(hidden_size=24, n_experts=8, k=3, activation="swiglu",
             dispatch="held", held=(1, 4, 6), bias=False, shared_hidden=24,
             route_scale=2.5),
    attention=dict(num_heads=4, num_kv_heads=2, head_dim=8, with_bias=False,
                   qk_norm=True, gated=True),
    window_attention=dict(num_heads=4, num_kv_heads=2, head_dim=8,
                          with_bias=False, qk_norm=True, gated=True,
                          rope=True, window=8),
    mlp=dict(hidden_size=48))


def _decoder(pattern, post_norm, seed=7):
    from bigdl_tpu.utils.rng import manual_seed
    manual_seed(seed)
    np.random.seed(seed)
    return nn.HybridDecoder(pattern, E, post_norm=post_norm, **KW)


def _loss(dec, x):
    def f(p):
        y = functional_apply(dec, p, dec.buffer_tree(), x, training=True)[0]
        return jnp.sum(jnp.square(y)) / y.size
    return f


def _always_flash(monkeypatch):
    """The attention blocks take the flash kernels whatever their size
    (off a TPU ``flash_attention`` runs them in Pallas' interpreter)."""
    monkeypatch.setattr(fa, "use_flash", lambda q, mask: mask is None)


@pytest.fixture
def flash_in_the_interpreter(monkeypatch):
    _always_flash(monkeypatch)


# ------------------------------------------------- (a) no number changes

@pytest.mark.parametrize("kind,post_norm,flash", [
    ("M", False, False), ("E", False, False), ("E", True, False),
    ("*", False, False), ("*", True, True), ("W", True, False),
    ("W", True, True), ("-", True, False), ("-", False, False),
    ("C", False, False)])
def test_keeping_changes_neither_the_loss_nor_a_gradient(
        kind, post_norm, flash, monkeypatch):
    """Two blocks of one kind, float32: the loss and every gradient leaf
    under ``remat_blocks`` are those without it."""
    if flash:
        _always_flash(monkeypatch)
    dec = _decoder(kind * 2, post_norm)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 32, E)).astype(np.float32))

    def run(ckpt):
        dec.remat_blocks = ckpt
        return jax.jit(jax.value_and_grad(_loss(dec, x)))(
            dec.parameter_tree())

    (loss, grads), (loss_k, grads_k) = run(False), run(True)
    np.testing.assert_allclose(float(loss_k), float(loss), rtol=1e-5)
    flat, flat_k = (jax.tree_util.tree_leaves_with_path(g)
                    for g in (grads, grads_k))
    assert len(flat) == len(flat_k) > 0
    for (path, a), (_, b) in zip(flat, flat_k):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(
            b, a, rtol=1e-5, atol=1e-5 * max(np.abs(a).max(), 1e-30),
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------- (b) the names are honoured, by counting

def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _eqns(jaxpr):
    """Every equation of a jaxpr; checkpointed bodies, loops, branches and
    custom rules included, a kernel's own body not."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in _sub_jaxprs(eqn):
                yield from _eqns(inner)


def _rehearsal_model(cell_name):
    cell, cfg = harness.load_cell(cell_name, rehearse=True)
    builder = harness.load_builder(cfg["family"])
    model = builder.build(cfg, 3)
    dec = builder.decoder_of(model)
    assert dec.remat_blocks         # the configuration's training.remat
    return cell, cfg, model, dec


def _step_jaxpr(cell, cfg, model):
    """The differentiated training loss of the rehearsal model, as the
    step program differentiates it."""
    from benchmark.kinds.train import _policy
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    builder = harness.load_builder(cfg["family"])
    data, labels = builder.reference_batch(cfg, cell, 3)
    criterion = builder.criterion(cfg)

    def f(params, buffers, data, labels):
        loss_fn = make_training_loss_fn(
            model, criterion, _policy("fp32"), (), False, buffers,
            jax.random.PRNGKey(0), data, labels)
        return jax.grad(loss_fn, has_aux=True)(params)

    return jax.make_jaxpr(f)(model.parameter_tree(), model.buffer_tree(),
                             jnp.asarray(data), jnp.asarray(labels)).jaxpr


def _work(jaxpr, mamba_width=None):
    """How often the differentiated step runs what the list is for: the
    kernels by name, the routing's top-k and sort, a Mamba-2
    in-projection; and how often it meets each tag (a value that is kept
    is tagged in the forward alone, one that is not is tagged again in the
    backward's second forward, if the backward reads it)."""
    seen = collections.Counter()
    for eqn in _eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "pallas_call":
            seen[eqn.params["name"]] += 1
        elif prim in ("sort", "top_k"):
            seen[prim] += 1
        elif prim == "name":
            seen["tag:" + eqn.params["name"]] += 1
        elif (prim == "dot_general" and mamba_width is not None
              and eqn.outvars[0].aval.shape[-1] == mamba_width
              and len(eqn.outvars[0].aval.shape) == 3):
            seen["mamba_in_proj"] += 1
    return seen


#: tags a forward of each rehearsal model meets, by name: three attention
#: blocks of five projections and two flash outputs, a dense block of
#: three, two expert blocks of five tables, one output and a SwiGLU shared
#: expert's two products; one Mamba-2, one expert (a relu² shared expert's
#: one product) and one attention block (no gate)
MET = {"trinity-mini-train-s8192": {
           remat.ATTN_PROJ: 15, remat.FLASH_OUT: 6, remat.MLP_PROJ: 3,
           remat.MOE_ROUTE_TABLES: 10, remat.MOE_ROUTED_OUT: 2,
           remat.MOE_SHARED_HID: 4},
       "nemotron-3-nano-30b-a3b-train-s8192": {
           remat.MAMBA_IN_PROJ: 1, remat.MOE_ROUTE_TABLES: 5,
           remat.MOE_ROUTED_OUT: 1, remat.MOE_SHARED_HID: 1,
           remat.ATTN_PROJ: 4, remat.FLASH_OUT: 2},
       # four convolution mixers' in-projections, a dense block of three,
       # one attention block (no gate), four expert blocks whose picks are
       # a table's (four tables each, no top-k) and have no shared expert
       "lfm2-24b-a2b-train-s8192": {
           remat.SHORT_CONV_IN_PROJ: 4, remat.MLP_PROJ: 3,
           remat.ATTN_PROJ: 4, remat.FLASH_OUT: 2,
           remat.MOE_ROUTE_TABLES: 20, remat.MOE_ROUTED_OUT: 4},
       # three delta-rule mixers' in-projections, four dense blocks of
       # three, one attention block (no gate)
       "olmo-hybrid-7b-train-s8192": {
           remat.DELTA_IN_PROJ: 3, remat.MLP_PROJ: 12,
           remat.ATTN_PROJ: 4, remat.FLASH_OUT: 2}}


CELLS = {"trinity-mini-train-s8192": "W-WE*E",
         "nemotron-3-nano-30b-a3b-train-s8192": "ME*"}


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_the_differentiated_step_runs_kept_work_once_a_block(
        cell_name, flash_in_the_interpreter, monkeypatch):
    """With the list as it is, each flash forward kernel, each expert
    block's top-k and sort and each Mamba-2 in-projection appears ONCE a
    block in the differentiated step; with the list emptied (what a tag
    whose name has drifted from the list amounts to) each appears twice."""
    cell, cfg, model, dec = _rehearsal_model(cell_name)
    pattern = dec.pattern
    assert pattern == CELLS[cell_name]
    n = collections.Counter(pattern)
    mamba = next((m for m in model.modules() if isinstance(m, nn.Mamba2)),
                 None)
    width = mamba.in_proj_weight.shape[0] if mamba is not None else None
    once = {"flash_band_fwd": n["W"], "flash_fwd": n["*"], "sort": n["E"],
            "top_k": n["E"], "mamba_in_proj": n["M"],
            # never kept, once a block either way
            "flash_band_bwd_dkv": n["W"], "flash_bwd_dkv": n["*"]}
    once = {k: v for k, v in once.items() if v}
    kept = _work(_step_jaxpr(cell, cfg, model), width)
    assert {k: kept[k] for k in once} == once
    assert not [k for k in kept if k.endswith("bwd_dq")]  # one backward call
    assert {k[4:]: v for k, v in kept.items() if k.startswith("tag:")} \
        == MET[cell_name]
    monkeypatch.setattr(
        hybrid, "block_remat_policy",
        lambda through=None:
        jax.checkpoint_policies.save_only_these_names("drifted"))
    twice = _work(_step_jaxpr(cell, cfg, model), width)
    for k, v in once.items():
        assert twice[k] == (v if "bwd" in k else 2 * v), (k, twice)
    # the routed experts' output is read again only by a norm on the
    # block's output (no backward of the layer itself reads it)
    unread = set() if "norm_post" in dec.layer0._modules \
        else {remat.MOE_ROUTED_OUT}
    for name, met in MET[cell_name].items():
        assert twice["tag:" + name] == met if name in unread \
            else twice["tag:" + name] > met, (name, twice)


# --------------------------- (c) the tags and the list, (d) the counter

def _tags_and_counts(cell_name):
    cell, cfg, model, dec = _rehearsal_model(cell_name)
    fam = instruments(get_registry()).remat_kept_total
    before = {n: fam.labels(name=n).value for n in remat.BLOCK_SAVED_NAMES}
    jaxpr = _step_jaxpr(cell, cfg, model)
    rise = {n: fam.labels(name=n).value - before[n]
            for n in remat.BLOCK_SAVED_NAMES}
    tagged = {eqn.params["name"] for eqn in _eqns(jaxpr)
              if eqn.primitive.name == "name"}
    return tagged, {n: int(v) for n, v in rise.items() if v}


def test_every_tag_is_on_the_list_and_every_listed_name_is_tagged(
        flash_in_the_interpreter):
    tagged = set()
    for cell_name in MET:
        tagged |= _tags_and_counts(cell_name)[0]
    assert tagged == set(remat.BLOCK_SAVED_NAMES)
    with pytest.raises(ValueError):
        remat.keep(jnp.ones(()), "no_such_name")
    with pytest.raises(ValueError):     # a producer's name, not block's
        remat.keep(jnp.ones(()), "router_logits")


@pytest.mark.parametrize("cell_name", sorted(MET))
def test_the_counter_reads_the_tags_a_trace_met(cell_name,
                                                flash_in_the_interpreter):
    """``bigdl_remat_kept_total{name}`` rises once a tag a trace: the
    forward's tags once (the backward's second forward is jax's replay of
    the traced jaxpr, not a second trace)."""
    assert _tags_and_counts(cell_name)[1] == MET[cell_name]


def test_a_tag_outside_a_checkpoint_lowers_to_nothing():
    """No ``jax.checkpoint`` around it (the Qwen cell, serving): the tagged
    forward lowers to the text of the untagged one."""
    m = nn.MultiHeadAttention(E, 4, causal=True, with_bias=False,
                              gated=True, qk_norm=True)
    x = jnp.ones((1, 16, E))

    def lowered():
        return jax.jit(lambda p: functional_apply(
            m, p, m.buffer_tree(), x)[0]).lower(m.parameter_tree()).as_text()

    tagged = lowered()
    real = attention.keep
    attention.keep = lambda value, name: value
    try:
        assert lowered() == tagged
    finally:
        attention.keep = real


def test_a_convnet_carries_no_tag():
    """A tag stands only where a policy lists it: the convolutions and the
    training batch norm, which carried the names of a policy no cell
    selected until PR 44, trace to no ``name`` equation."""
    m = nn.Sequential().add(nn.SpaceToDepthConv7(3, 8)) \
        .add(nn.SpatialBatchNormalization(8)).add(nn.ReLU()) \
        .add(nn.SpatialConvolution(8, 8, 3, 3))
    x = jnp.ones((2, 16, 16, 3))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(functional_apply(
        m, p, m.buffer_tree(), x, training=True)[0])))(m.parameter_tree())
    names = [eqn.primitive.name for eqn in _eqns(jaxpr.jaxpr)]
    assert "conv_general_dilated" in names and "name" not in names
