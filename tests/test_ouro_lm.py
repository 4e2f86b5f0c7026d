"""The Ouro family (a LOOPED decoder: every layer a full-attention block and
a dense SwiGLU block with four norms, the whole stack run ``total_ut_steps``
times over the same weights with the final norm between passes, one exit
gate, a loss that weighs every pass's cross-entropy by a learned exit
distribution) against its plain reference (``benchmark/reference/ouro.py``),
at small sizes on the CPU; the loop as a scan and written out; block remat
with a shortened keep list; the configuration's sizes; its cell's rehearsal
and negative controls."""

import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import flops_ouro, harness
from benchmark.builders import ouro as builder
from benchmark.reference import ouro as reference
from bigdl_tpu import nn
from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.ops.precision import DtypePolicy
from test_block_remat import _eqns

logging.getLogger("bigdl_tpu.optim").setLevel(logging.WARNING)

CELL = "ouro-2.6b-train-s4096"


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


def _rehearsal(**training):
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, dict(cfg, training=dict(cfg["training"], **training))


@pytest.fixture(scope="module")
def cut():
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return cell, cfg, builder.build(cfg, 3)


def _system_grads(model, cfg, cell, policy=DtypePolicy()):
    from bigdl_tpu.optim.optimizer import make_training_loss_fn
    data, labels = builder.reference_batch(cfg, cell, 3)

    @jax.jit
    def run(params, buffers, data, labels):
        loss_fn = make_training_loss_fn(
            model, builder.criterion(cfg), policy, (), False, buffers,
            jax.random.PRNGKey(0), data, labels)
        grads, (_, loss) = jax.grad(loss_fn, has_aux=True)(params)
        return loss, grads

    loss, grads = run(model.parameter_tree(), model.buffer_tree(),
                      jnp.asarray(data), jnp.asarray(labels))
    return loss, grads, data, labels


# ------------------------------------------------------- the configuration

def test_the_file_holds_the_rows_keys_and_cuts_depth_alone():
    """Every key of the catalogue's row as published but the depth; the
    published widths, the whole vocabulary, all four passes; the cut and
    what block remat keeps are stated."""
    cell, cfg = harness.load_cell(CELL)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 48
    row = dict(head_dim=128, hidden_act="silu", hidden_size=2048,
               intermediate_size=5632, max_position_embeddings=65536,
               max_window_layers=48, model_type="ouro",
               num_attention_heads=16, num_key_value_heads=16,
               rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
               sliding_window=None, tie_word_embeddings=False,
               total_ut_steps=4, early_exit_threshold=1,
               use_sliding_window=False, vocab_size=49152)
    assert {k: cfg[k] for k in row} == row
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == ["full_attention"] * 8
    assert set(cfg["assumed"]) >= {"block", "loop", "exit_gate", "loss",
                                   "rotary", "bias", "weights"}
    assert "six" in cfg["deployment"].lower()
    from bigdl_tpu.ops.remat import BLOCK_SAVED_NAMES
    assert cfg["training"]["remat"] == "block"
    assert cfg["training"]["remat_keep_through"] in BLOCK_SAVED_NAMES
    assert (cell["batch_size"], cell["seq_len"]) == (1, 4096)
    assert cell["reference"]["seq_len"] == cell["seq_len"]
    assert set(cell["reference"]["blocks"]) == {"layer", "exit_loss"}


def test_the_parameter_count_is_the_files_and_the_builds(cut):
    """612,438,017 at the published widths by shapes; at the rehearsal
    size the same count is what the built model holds."""
    _, cfg, model = cut
    assert flops_ouro.parameters(harness.load_cell(CELL)[1]) == 612_438_017
    held = sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(model.parameter_tree()))
    assert held == flops_ouro.parameters(cfg)


def test_the_rehearsal_keeps_what_the_cell_is_about(cut):
    """Sandwich blocks ``*-`` a layer, rotation, an untied head, four
    passes as one scan, the exit gate with a bias, block remat keeping a
    leading part of its list."""
    cell, cfg, model = cut
    dec = builder.decoder_of(model)
    assert dec.pattern == "*-" * cfg["num_hidden_layers"]
    assert dec.passes == 4
    assert dec.remat_blocks
    assert dec.remat_keep_through == cfg["training"]["remat_keep_through"]
    for i in range(dec.num_layers):
        block = dec._modules[f"layer{i}"]
        assert {"norm", "norm_post"} <= set(block._modules)
    attn = dec._modules["layer0"].mixer
    assert attn.rope and attn.rope_theta == 1e6
    assert isinstance(model._ordered[-1], nn.LMHead)
    assert model.exit_gate.weight.shape == (1, cfg["hidden_size"])
    assert model.exit_gate.bias.shape == (1,)
    assert model.exit_beta == 0.1


def test_ouro_lm_kwargs_on_the_rows_own_keys():
    """``interop.hf.ouro_lm_kwargs`` on the PUBLISHED config (48 layers):
    the pattern, the groups, the loop; what it does not map is refused."""
    from bigdl_tpu.interop.hf import ouro_lm_kwargs
    _, cfg = harness.load_cell(CELL)
    row = dict(cfg, num_hidden_layers=48,
               layer_types=["full_attention"] * 48)
    kw = ouro_lm_kwargs(row)
    assert kw["pattern"] == "*-" * 48
    assert (kw["vocab_size"], kw["embed_dim"]) == (49152, 2048)
    assert kw["attention"] == dict(num_heads=16, num_kv_heads=16,
                                   head_dim=128, with_bias=False, rope=True,
                                   rope_theta=1e6)
    assert kw["mlp"] == {"hidden_size": 5632}
    assert (kw["passes"], kw["exit_gate"], kw["exit_beta"]) == (4, True, 0.1)
    assert kw["post_norm"] and kw["norm_eps"] == 1e-6
    for bad in (dict(layer_types=["sliding_attention"] * 48),
                dict(use_sliding_window=True), dict(hidden_act="gelu"),
                dict(rope_scaling={"type": "yarn"}),
                dict(tie_word_embeddings=True),
                dict(early_exit_threshold=0.5), dict(num_hidden_layers=47)):
        with pytest.raises(ValueError):
            ouro_lm_kwargs(dict(row, **bad))


# ------------------------------------------------ against the plain reference

@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_loss_and_every_gradient_leaf_match_the_reference(passes):
    """The program's own training loss in float32 against the plain
    reference on seeded weights at batch 2: the loss, and EACH leaf of the
    gradient under the reference's names (a shared weight's is the sum of
    its ``passes`` terms, formed by the scan's transpose), under block
    remat."""
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cell = dict(cell, reference=dict(cell["reference"], batch=2))
    cfg = dict(cfg, total_ut_steps=passes)
    model = builder.build(cfg, 3)
    loss, grads, data, labels = _system_grads(model, cfg, cell)
    assert data.shape[0] == 2
    ids, tgt = (jnp.asarray(t, jnp.int32) - 1 for t in (data, labels))
    want, want_g = jax.jit(lambda q: reference.loss_and_grad(
        q, ids, tgt, cfg, builder.exit_beta(cfg)))(
            builder.reference_params(model))
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got_g = builder.named(grads)
    assert sorted(got_g) == sorted(want_g)
    assert len(got_g) == len(jax.tree_util.tree_leaves(grads))
    for k in want_g:
        if passes == 1 and "early_exit_gate" in k:
            # the last pass's gate reads no loss: with one pass, no gate does
            assert not np.asarray(got_g[k]).any()
            assert not np.asarray(want_g[k]).any()
            continue
        assert np.asarray(want_g[k]).any(), k
        _close(got_g[k], want_g[k], tol=2e-4)


def test_one_pass_with_the_gate_is_the_unlooped_model(cut):
    """``passes=1`` with the gate: one pass takes the whole exit
    distribution, its entropy is 0, and the loss and every shared gradient
    are those of the unlooped ``build_hybrid_lm`` under
    ``FusedLMHeadCriterion``."""
    from bigdl_tpu.interop.hf import ouro_lm_kwargs
    from bigdl_tpu.models.hybrid import build_hybrid_lm
    from bigdl_tpu.utils.rng import manual_seed
    cell, cfg, _ = cut
    kw = ouro_lm_kwargs(dict(cfg, total_ut_steps=1))
    plain_kw = {k: v for k, v in kw.items()
                if k not in ("passes", "exit_gate", "exit_beta")}
    read = []
    for kwargs in (kw, plain_kw):
        manual_seed(5)
        np.random.seed(5)
        model = build_hybrid_lm(**kwargs)
        read.append(_system_grads(model, cfg, cell)[:2])
    (loss, grads), (want, want_g) = read
    assert "exit_gate" in grads and "exit_gate" not in want_g
    assert abs(float(loss) - float(want)) < 1e-6 * float(want)
    for k in want_g:
        jax.tree_util.tree_map(lambda a, b: _close(a, b, tol=1e-5),
                               grads[k], want_g[k])


def test_eval_is_the_last_passes_log_probs(cut):
    cell, cfg, model = cut
    data, _ = builder.reference_batch(cfg, cell, 3)
    got = functional_apply(model, model.parameter_tree(),
                           model.buffer_tree(), jnp.asarray(data),
                           training=False)[0]
    want = reference.log_probs(builder.reference_params(model),
                               jnp.asarray(data, jnp.int32) - 1, cfg)
    assert got.shape == want.shape == (1, 32, cfg["vocab_size"])
    _close(got, want, tol=1e-5)


def test_one_layer_matches_the_reference(cut):
    """The decoder's first two blocks are the reference's ``layer``: both
    residual blocks, all four norms, rotation in the half-split layout."""
    _, cfg, model = cut
    dec = builder.decoder_of(model)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 32, cfg["hidden_size"])).astype(np.float32))
    y = x
    for i in (0, 1):
        block = dec._modules[f"layer{i}"]
        y = functional_apply(block, block.parameter_tree(),
                             block.buffer_tree(), y, training=True)[0]
    want = reference.layer(builder.reference_params(model), 0, x, cfg)
    _close(y, want, tol=1e-5)


def test_the_exit_distribution_is_the_references():
    """The criterion's log-sigmoid form against the reference's products:
    a distribution over the passes whose last entry takes what is left;
    one pass takes it all."""
    from bigdl_tpu.nn.criterion import exit_distribution
    g = jnp.asarray(3.0 * np.random.default_rng(1).standard_normal(
        (4, 2, 16)).astype(np.float32))
    p, neg_h = exit_distribution(g)
    want = reference.exit_distribution(g)
    _close(p, want, tol=1e-6)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(g)
    _close(p[-1], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), tol=1e-6)
    _close(-neg_h, reference.entropy(want), tol=1e-5)
    p1, h1 = exit_distribution(g[:1])
    assert np.asarray(p1 == 1.0).all() and np.asarray(h1 == 0.0).all()


# ------------------------------------------ the loop written out, and remat

def _written_out(monkeypatch):
    """The pass loop written out from ``dec.stream`` and ``final_norm``:
    the scaffold of the controls (``builders/ouro._faulty_passes``) with no
    fault in it."""
    monkeypatch.setattr(nn.HybridDecoder, "pass_streams",
                        builder._faulty_passes(None))


@pytest.mark.parametrize("remat", ["block", None])
def test_the_scan_and_the_written_out_loop_agree(remat, monkeypatch):
    """The scan is a way to hold ONE copy of the stack, not another
    value: the loss and every gradient leaf are the written-out loop's;
    the counter counts the scan's trace."""
    from bigdl_tpu.telemetry import get_registry, instruments
    counter = instruments(get_registry()).decoder_passes_total
    cell, cfg = _rehearsal(remat=remat)
    before = counter.value
    loss, grads = _system_grads(builder.build(cfg, 3), cfg, cell)[:2]
    assert counter.value - before == 1
    _written_out(monkeypatch)
    want, want_g = _system_grads(builder.build(cfg, 3), cfg, cell)[:2]
    assert counter.value - before == 1
    assert abs(float(loss) - float(want)) < 1e-6 * float(want)
    jax.tree_util.tree_map(lambda a, b: _close(a, b, tol=1e-5), grads,
                           want_g)


def test_the_scan_holds_one_copy_of_the_stack(monkeypatch):
    """The traced program holds each block once, inside one loop over the
    passes; written out it would hold them ``passes`` times."""
    _, cfg = _rehearsal(remat=None)
    dec = builder.decoder_of(builder.build(cfg, 3))
    x = jnp.zeros((1, 32, cfg["hidden_size"]))

    def prods():
        return str(jax.make_jaxpr(lambda p: functional_apply(
            dec, p, dec.buffer_tree(), x, training=True)[0])(
                dec.parameter_tree())).count("dot_general")

    once = prods()
    _written_out(monkeypatch)
    assert prods() == dec.passes * once


def _tags(through):
    """How often the differentiated, rematerialised forward of the
    rehearsal decoder meets each tag (a value that is kept is tagged in
    the forward alone, one that is not is tagged again in the backward's
    second forward, where the backward reads it)."""
    import collections
    _, cfg = _rehearsal(remat_keep_through=through)
    dec = builder.decoder_of(builder.build(cfg, 3))
    x = jnp.zeros((1, 32, cfg["hidden_size"]))

    def f(p):
        return jnp.sum(functional_apply(dec, p, dec.buffer_tree(), x,
                                        training=True)[0])

    return collections.Counter(
        e.params["name"] for e in _eqns(jax.make_jaxpr(jax.grad(f))(
            dec.parameter_tree()).jaxpr) if e.primitive.name == "name")


def test_a_shortened_keep_list_keeps_a_leading_part_and_changes_no_value():
    """``remat_keep_through`` names the last of ``BLOCK_SAVED_NAMES`` a
    decoder keeps: through ``attn_proj`` the dense blocks' products run
    again, through ``flash_out`` the attention's too; the loss and every
    gradient leaf are those of the whole list."""
    from bigdl_tpu.ops.remat import ATTN_PROJ, MLP_PROJ, block_remat_policy
    whole, attn, flash = (_tags(t) for t in (None, "attn_proj", "flash_out"))
    assert whole[ATTN_PROJ] > 0 and whole[MLP_PROJ] > 0
    assert attn[ATTN_PROJ] == whole[ATTN_PROJ]
    assert attn[MLP_PROJ] > whole[MLP_PROJ]
    assert flash[ATTN_PROJ] > whole[ATTN_PROJ]
    assert flash[MLP_PROJ] == attn[MLP_PROJ]
    with pytest.raises(ValueError, match="not on block remat's list"):
        block_remat_policy("no_such_name")
    read = []
    for through in (None, "flash_out"):
        cell, cfg = _rehearsal(remat_keep_through=through)
        read.append(_system_grads(builder.build(cfg, 3), cfg, cell)[:2])
    (loss, grads), (want, want_g) = read
    assert float(loss) == float(want)
    jax.tree_util.tree_map(lambda a, b: _close(a, b, tol=1e-6), grads,
                           want_g)


# --------------------------------------------------- the cell and its gate

#: the block limits in float32, where the sound system is the reference
_TIGHT = {kind: {"out_rtol": 1e-4, "grad_rtol": 2e-4}
          for kind in ("layer", "exit_loss")}


def _fp32(cell):
    return dict(cell, precision="fp32",
                reference=dict(cell["reference"], loss_rtol=1e-5,
                               grad_norm_rtol=1e-4, blocks=_TIGHT))


@pytest.fixture(scope="module")
def controlled():
    """``benchmark.controls`` at the rehearsal size in float32 (there the
    sound system is the reference to 1e-6)."""
    from benchmark import controls
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    return dict(controls.run(_fp32(cell), cfg, 3))


def test_the_sound_system_passes_the_reference_check(controlled):
    assert set(controlled) == {"sound", *builder.FAULTS}
    assert controlled["sound"]["ok"]


@pytest.mark.parametrize("fault", builder.FAULTS)
def test_a_fault_planted_in_the_system_goes_through_the_reference_check(
        controlled, fault):
    """Each fault is in the SYSTEM's modules (``reference_bf16``: the
    plain reference in bf16 where the system stood) and goes through the
    comparison that decides ``correct``; the reference is the sound one.
    In float32 at the rehearsal size every one of them fails it."""
    got, sound = controlled[fault], controlled["sound"]
    assert got["reference_loss"] == sound["reference_loss"]
    numbers = lambda r: (r["system_loss"], r["system_grad_norm"])
    assert numbers(got) != numbers(sound)   # one leaves the LOSS as it is
    assert not got["ok"]


#: the blocks that read each fault (the loop's are the whole model's to
#: see: a block is one layer application, or the loss over given streams)
_HIT = {"one_pass": set(), "no_norm_between_passes": set(),
        "last_pass_gradient_only": set(), "exit_uniform": {"exit_loss"},
        "no_entropy_term": {"exit_loss"}, "no_second_norms": {"layer"},
        "unweighted_rows": {"exit_loss"}}


@pytest.mark.parametrize("fault", sorted(_HIT))
def test_the_blocks_alone_refuse_a_fault_of_a_block(cut, fault):
    """The block check (one layer and the exit loss alone against the
    reference's at the cell's length) says not ok whatever the loss says:
    the builder then hands the train kind NaN for its two numbers."""
    cell, cfg, model = cut
    builder.reference_batch(cfg, _fp32(cell), 3)
    sound = builder.loop_blocks(model)
    assert set(sound) == {"layer", "exit_loss"}
    assert all(r["out"] < 2e-5 and r["grad"] < 1e-4 for r in sound.values())
    assert builder._gated((1.0, 2.0), model) == (1.0, 2.0)
    with builder.planted(model, fault):
        read = builder.loop_blocks(model)
        gated = builder._gated((1.0, 2.0), model)
    for kind, r in read.items():
        assert (max(r["out"], r["grad"]) > 1e-3) == (kind in _HIT[fault]), \
            (kind, r)
    assert np.isnan(gated).all() == bool(_HIT[fault])
    # a block the limits do not name is no pass
    builder.reference_batch(cfg, dict(_fp32(cell), reference=dict(
        cell["reference"], blocks={"layer": _TIGHT["layer"]})), 3)
    assert np.isnan(builder._gated((1.0, 2.0), model)).all()


def test_the_last_passes_gradient_alone_is_a_fault_of_the_gradient(cut):
    """``last_pass_gradient_only`` leaves the loss as it is and takes the
    earlier passes' terms out of every shared weight's gradient."""
    from benchmark.kinds import train as kind
    cell, cfg, model = cut
    data, labels = builder.reference_batch(cfg, _fp32(cell), 3)
    crit = builder.criterion(cfg)
    sound = kind.system_loss_and_grad_norm(model, crit, DtypePolicy(), data,
                                           labels)
    with builder.planted(model, "last_pass_gradient_only"):
        got = kind.system_loss_and_grad_norm(model, crit, DtypePolicy(),
                                             data, labels)
    assert abs(got[0] - sound[0]) < 1e-6 * sound[0]
    assert got[1] < 0.9 * sound[1]


def test_a_planted_fault_is_taken_out_again(cut):
    from benchmark.kinds import train as kind
    from bigdl_tpu.nn import criterion as criterion_rules
    from bigdl_tpu.ops import lm_head_ce
    _, _, model = cut
    dec = builder.decoder_of(model)
    state = lambda: (dec.passes, model.exit_beta,
                     nn.HybridDecoder.pass_streams,
                     nn.HybridBlock.update_output,
                     criterion_rules.exit_distribution,
                     lm_head_ce.fused_lm_head_ce,
                     kind.system_loss_and_grad_norm)
    before = state()
    for fault in builder.FAULTS:
        with builder.planted(model, fault):
            assert state() != before
        assert state() == before
    with pytest.raises(ValueError):
        with builder.planted(model, "no_such_fault"):
            pass


def test_the_cells_rehearsal_runs_to_its_line(capfd):
    """``python -m benchmark.run --workload <cell> --rehearse``: the whole
    control flow at the rehearsal size; exit code 3, one JSON line that
    names the CPU and is no measurement."""
    from benchmark import run
    rc = run.main(["--workload", CELL, "--seed", "2999999999", "--seconds",
                   "2", "--trace", "0", "--rehearse"])
    out, err = capfd.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 3 and line["rehearsal"] and not line["correct"]
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert set(line["metrics"]) == {"train_records_per_s", "setup_s"}
    detail = json.loads(next(
        ln for ln in err.splitlines()
        if ln.startswith("benchmark detail: ")).split(": ", 1)[1])
    checks = detail["checks"]
    assert checks["reference"]["ok"] and checks["loss_ok"]
    assert checks["one_step_compile"] and checks["compiles_in_window"] == 0
    blocks = json.loads(next(
        ln for ln in err.splitlines() if ln.startswith(
            "benchmark detail loop_blocks: ")).split(": ", 1)[1])
    assert blocks["ok"] and set(blocks) >= {"layer", "exit_loss"}


def test_a_traced_step_counts_one_loop_one_weighted_pass_and_eight_flash_calls(
        monkeypatch):
    """On a TPU backend, at a sequence the kernels take: tracing the
    model's training loss under block remat counts the scan once, the
    weighted fused pass once, and the jaxpr holds ONE flash forward a
    layer (not one a pass) under its name."""
    from bigdl_tpu.ops import flash_attention as fa
    from bigdl_tpu.telemetry import get_registry, instruments
    cell, cfg = harness.load_cell(CELL, rehearse=True)
    cfg = dict(cfg, hidden_size=128, num_attention_heads=1,
               num_key_value_heads=1, head_dim=128)
    model = builder.build(cfg, 3)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real = fa._flash_lse
    monkeypatch.setattr(fa, "_flash_lse", lambda *a: real(
        *a[:7], True, a[8]))            # the kernels in the interpreter
    ids = jnp.ones((1, 1024))
    ins = instruments(get_registry())
    count = lambda: (
        ins.decoder_passes_total.value,
        ins.lm_head_ce_total.labels(form="weighted_one_pass").value,
        ins.lm_head_ce_total.labels(form="one_pass").value,
        ins.flash_attention_total.labels(form="full").value)
    before = count()
    crit = builder.criterion(cfg)

    def f(p):
        out = functional_apply(model, p, model.buffer_tree(), ids,
                               training=True)[0]
        return crit.apply(out, ids)

    text = str(jax.make_jaxpr(jax.grad(f))(model.parameter_tree()))
    layers = cfg["num_hidden_layers"]
    assert tuple(a - b for a, b in zip(count(), before)) \
        == (1, 1, 0, layers)
    assert text.count("name=flash_fwd") == layers
    assert "name=flash_bwd_dkv" in text
