"""Fused LM-head cross-entropy: value/grad parity with the unfused tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import transformer
from bigdl_tpu.ops.lm_head_ce import fused_lm_head_ce, rows_per_tile

N, E, V = 24, 16, 37  # rows a tile of 7 and 16 leave a ragged last tile


def ref_ce(h, w, b, tgt, size_average=True, ignore_index=None):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, (tgt.astype(jnp.int32) - 1)[:, None], axis=1)[:, 0]
    if ignore_index is not None:
        valid = tgt.astype(jnp.int32) != ignore_index
        s = -jnp.sum(jnp.where(valid, picked, 0.0))
        return s / jnp.sum(valid) if size_average else s
    return -jnp.mean(picked) if size_average else -jnp.sum(picked)


def make_inputs(seed=0, n=N):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, E).astype(np.float32))
    w = jnp.asarray(rng.randn(V, E).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
    tgt = jnp.asarray(rng.randint(1, V + 1, (n,)).astype(np.float32))
    return h, w, b, tgt


class TestFusedOp:
    @pytest.mark.parametrize("chunk", [7, 16, 37, 64])
    def test_value_parity(self, chunk):
        h, w, b, tgt = make_inputs()
        got = fused_lm_head_ce(h, w, b, tgt, chunk=chunk)
        want = ref_ce(h, w, b, tgt)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    @pytest.mark.parametrize("chunk", [7, 37, 64])
    def test_grad_parity(self, chunk):
        h, w, b, tgt = make_inputs(1)
        gf = jax.grad(lambda h, w, b: fused_lm_head_ce(
            h, w, b, tgt, chunk=chunk), argnums=(0, 1, 2))(h, w, b)
        gr = jax.grad(lambda h, w, b: ref_ce(h, w, b, tgt),
                      argnums=(0, 1, 2))(h, w, b)
        for a, e in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=2e-5, rtol=1e-4)

    def test_no_bias(self):
        h, w, _, tgt = make_inputs(2)
        got = fused_lm_head_ce(h, w, None, tgt, chunk=16)
        want = ref_ce(h, w, None, tgt)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_sum_reduction(self):
        h, w, b, tgt = make_inputs(3)
        got = fused_lm_head_ce(h, w, b, tgt, chunk=16, size_average=False)
        want = ref_ce(h, w, b, tgt, size_average=False)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_ignore_index(self):
        h, w, b, tgt = make_inputs(4)
        tgt = tgt.at[::3].set(1.0)  # mark a third of rows with target 1
        got = fused_lm_head_ce(h, w, b, tgt, chunk=16, ignore_index=1)
        want = ref_ce(h, w, b, tgt, ignore_index=1)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        # ignored rows get zero hidden-gradient
        gh = jax.grad(lambda h: fused_lm_head_ce(
            h, w, b, tgt, chunk=16, ignore_index=1))(h)
        assert np.abs(np.asarray(gh)[::3]).max() == 0.0

    def test_3d_hidden(self):
        h, w, b, tgt = make_inputs(5)
        h3 = h.reshape(4, 6, E)
        t3 = tgt.reshape(4, 6)
        got = fused_lm_head_ce(h3, w, b, t3, chunk=16)
        want = ref_ce(h, w, b, tgt)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_bf16_hidden_finite_and_close(self):
        h, w, b, tgt = make_inputs(6)
        got = fused_lm_head_ce(h.astype(jnp.bfloat16),
                               w.astype(jnp.bfloat16), b, tgt, chunk=16)
        want = ref_ce(h, w, b, tgt)
        assert np.isfinite(float(got))
        np.testing.assert_allclose(float(got), float(want), rtol=0.05)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (scan bodies,
    custom_vjp calls, pjit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _vocab_products(fn, *args):
    """dot_generals of ``fn``'s jaxpr with V among their shapes."""
    return [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "dot_general"
            and any(V in v.aval.shape for v in (*e.invars, *e.outvars))]


class TestOnePass:
    @pytest.mark.parametrize("chunk", [8, None])   # three tiles; one
    def test_gradient_holds_three_products_and_the_primal_one(self, chunk):
        h, w, b, tgt = make_inputs(7)

        def loss(h, w, b):
            return fused_lm_head_ce(h, w, b, tgt, chunk=chunk)

        assert len(_vocab_products(
            jax.grad(loss, argnums=(0, 1, 2)), h, w, b)) == 3
        assert len(_vocab_products(loss, h, w, b)) == 1

    def test_no_vocabulary_padding(self):
        """W enters every product at its own V: no array of the gradient
        program is wider than the inputs."""
        h, w, b, tgt = make_inputs(8)
        jaxpr = jax.make_jaxpr(jax.grad(lambda h, w, b: fused_lm_head_ce(
            h, w, b, tgt, chunk=8), argnums=(0, 1, 2)))(h, w, b).jaxpr
        assert not [e for e in _eqns(jaxpr) if e.primitive.name == "pad"]
        assert max(d for e in _eqns(jaxpr) for v in e.outvars
                   for d in v.aval.shape) == V

    @pytest.mark.parametrize("shape,tiles", [
        ((4096, 896, 151936), 1),      # the Qwen cell
        ((8192, 2688, 16384), 1),      # the Nemotron cell
        ((16384, 896, 151936), 4),     # the Qwen cell at batch 8
        ((N, E, V), 1)])
    def test_tile_rule_at_the_cells_shapes(self, shape, tiles):
        n, _, v = shape
        rows = rows_per_tile(n, v)
        assert rows * tiles == n
        assert rows >= min(n, 1024)     # under that dW's traffic binds
        assert rows_per_tile(n, v, 7) == 7
        assert rows_per_tile(n, v, 10 ** 9) == n    # clamps to one tile

    def test_ragged_tile_with_ignored_rows_in_the_padding(self):
        """N = 24 in tiles of 7: the last tile holds 3 rows and 4 of
        padding, which must count as ignored rows do."""
        h, w, b, tgt = make_inputs(9)
        tgt = tgt.at[::5].set(1.0).at[-1].set(1.0)

        def both(fn):
            return jax.value_and_grad(fn, argnums=(0, 1, 2))(h, w, b)

        got, g_got = both(lambda h, w, b: fused_lm_head_ce(
            h, w, b, tgt, chunk=7, ignore_index=1))
        want, g_want = both(lambda h, w, b: ref_ce(
            h, w, b, tgt, ignore_index=1))
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        for a, e in zip(g_got, g_want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=2e-5, rtol=1e-4)

    def test_no_bias_builds_no_db_and_grad_wrt_hidden_alone(self):
        h, w, _, tgt = make_inputs(10)

        def loss(h, w):
            return fused_lm_head_ce(h, w, None, tgt, chunk=8)

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w).jaxpr
        assert not [e for e in _eqns(jaxpr)
                    if e.primitive.name == "reduce_sum"
                    and e.outvars[0].aval.shape == (V,)]
        gh = jax.grad(loss)(h, w)
        want = jax.grad(lambda h: ref_ce(h, w, None, tgt))(h)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_upstream_cotangent_scales_all_three_gradients(self):
        h, w, b, tgt = make_inputs(11)
        gf = jax.grad(lambda h, w, b: 3.0 * fused_lm_head_ce(
            h, w, b, tgt, chunk=16), argnums=(0, 1, 2))(h, w, b)
        gr = jax.grad(lambda h, w, b: ref_ce(h, w, b, tgt),
                      argnums=(0, 1, 2))(h, w, b)
        for a, e in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), 3.0 * np.asarray(e),
                                       atol=6e-5, rtol=1e-4)

    def test_counter_names_the_form(self):
        from bigdl_tpu.telemetry import get_registry, instruments
        total = instruments(get_registry()).lm_head_ce_total
        h, w, b, tgt = make_inputs(12)

        def counts():
            return [total.labels(form=f).value
                    for f in ("one_pass", "forward_only")]

        def loss(h):
            return fused_lm_head_ce(h, w, b, tgt)

        before = counts()
        loss(h)
        assert counts() == [before[0], before[1] + 1]
        grad = jax.jit(jax.grad(loss))
        grad(h)
        grad(h)                         # counted once a compiled program
        assert counts() == [before[0] + 1, before[1] + 1]


class TestCriterionAndHead:
    def test_head_train_emits_table_eval_logprobs(self):
        head = nn.LMHead(E, V)
        h = jnp.ones((2, 3, E))
        out = head.forward(h)
        assert len(out) == 3  # Table(hidden, weight, bias)
        head.evaluate_mode()
        logp = head.forward(h)
        assert logp.shape == (2, 3, V)
        np.testing.assert_allclose(
            np.asarray(jnp.exp(logp).sum(-1)), 1.0, rtol=1e-5)

    def test_criterion_matches_time_distributed_nll(self):
        rng = np.random.RandomState(7)
        h = jnp.asarray(rng.randn(2, 5, E).astype(np.float32))
        tgt = jnp.asarray(rng.randint(1, V + 1, (2, 5)).astype(np.float32))
        head = nn.LMHead(E, V)
        fused = nn.FusedLMHeadCriterion(chunk=16).apply(head.forward(h), tgt)
        head.evaluate_mode()
        logp = head.forward(h)
        # default size_average=False: inner NLL already averages over the
        # merged batch*time axis -> flat mean, which is what fused computes
        ref = nn.TimeDistributedCriterion(
            nn.ClassNLLCriterion()).apply(logp, tgt)
        np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5)
        # eval fallback: same criterion instance scores log-probs directly
        fb = nn.FusedLMHeadCriterion(chunk=16).apply(logp, tgt)
        np.testing.assert_allclose(float(fb), float(ref), rtol=1e-5)

    def test_fused_model_trains_with_loss_parity(self):
        """One SGD step on fused vs unfused tails with identical weights
        produces the same loss trajectory."""
        from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
        from bigdl_tpu.optim import SGD, Optimizer, Trigger

        rng = np.random.RandomState(0)
        vocab, s = 19, 6
        feats = [rng.randint(1, vocab + 1, (s,)).astype(np.float32)
                 for _ in range(8)]
        samples = [Sample(f, rng.randint(1, vocab + 1, (s,))
                          .astype(np.float32)) for f in feats]

        def run(fused):
            from bigdl_tpu.utils.rng import manual_seed
            manual_seed(123)  # identical shuffle order across both runs
            m = transformer.build_lm(vocab, 8, 2, 16, num_layers=1,
                                     max_len=16, fused_head=fused)
            # identical init across both builds
            from jax.flatten_util import ravel_pytree
            seed_tree = m.parameter_tree()
            flat, unravel = ravel_pytree(seed_tree)
            m.load_parameter_tree(unravel(
                jnp.asarray(np.random.RandomState(42)
                            .randn(flat.size).astype(np.float32) * 0.1)))
            crit = (nn.FusedLMHeadCriterion(chunk=8) if fused else
                    nn.TimeDistributedCriterion(nn.ClassNLLCriterion()))
            ds = DataSet.array(samples).transform(SampleToBatch(batch_size=4))
            losses = []

            class Rec:
                def add_scalar(self, tag, v, step):
                    if tag == "Loss":
                        losses.append(float(v))

                def get_summary_trigger(self, name):
                    return None

            opt = Optimizer(m, ds, crit)
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_train_summary(Rec())
            opt.set_end_when(Trigger.max_iteration(4))
            opt.optimize()
            return losses

        np.testing.assert_allclose(run(True), run(False), rtol=2e-4)


class TestTiedEmbeddings:
    def test_one_shared_matrix(self):
        m = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=16,
                                 tie_embeddings=True)
        untied = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=16,
                                      fused_head=True)
        assert m.n_parameters() == untied.n_parameters() - V * E - V

    def test_gradient_combines_both_uses(self):
        """d loss/d table must include the embedding AND head paths: it
        differs from the untied head-gradient alone."""
        from bigdl_tpu.nn.module import functional_apply
        m = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=16,
                                 tie_embeddings=True)
        crit = nn.FusedLMHeadCriterion(chunk=16)
        params, buffers = m.functional_state()
        x = jnp.asarray([[3.0, 5.0, 7.0]])
        y = jnp.asarray([[5.0, 7.0, 2.0]])

        def loss(p):
            out, _ = functional_apply(m, p, buffers, x, training=True)
            return crit.apply(out, y)

        g = jax.grad(loss)(params)
        table_grad = g["0"]["weight"]  # Sequential child 0 = LookupTable
        # head path touches every vocab row; rows NOT in the prompt get
        # gradient only via the head -> nonzero beyond the embedded rows
        untouched = np.asarray(table_grad)[10:]  # rows 11.. never embedded
        assert np.abs(untouched).max() > 0

    def test_tied_generate_and_eval(self):
        m = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=32,
                                 tie_embeddings=True)
        from bigdl_tpu.models.generation import generate
        out = generate(m, jnp.ones((1, 3)), 5, greedy=True)
        assert out.shape == (1, 8)
        logp = m.evaluate_mode().predict(jnp.ones((1, 4)))
        np.testing.assert_allclose(np.asarray(jnp.exp(logp).sum(-1)), 1.0,
                                   rtol=1e-5)

    def test_tying_survives_clone_and_pickle(self):
        import pickle
        m = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=16,
                                 tie_embeddings=True)
        for copy_fn in (lambda x: x.clone_module(),
                        lambda x: pickle.loads(pickle.dumps(x))):
            c = copy_fn(m)
            head = [mm for mm in c.modules()
                    if type(mm).__name__ == "TiedLMHead"][0]
            emb = [mm for mm in c.modules()
                   if type(mm).__name__ == "LookupTable"][0]
            assert head.embed_ref is emb  # sharing preserved

    def test_tied_trains_e2e(self):
        from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
        from bigdl_tpu.optim import SGD, Optimizer, Trigger
        rng = np.random.RandomState(0)
        samples = [Sample(rng.randint(1, V + 1, (6,)).astype(np.float32),
                          rng.randint(1, V + 1, (6,)).astype(np.float32))
                   for _ in range(8)]
        m = transformer.build_lm(V, E, 2, 16, num_layers=1, max_len=16,
                                 tie_embeddings=True)
        opt = Optimizer(m, DataSet.array(samples).transform(
            SampleToBatch(batch_size=4)), nn.FusedLMHeadCriterion(chunk=16))
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(3))
        opt.optimize()


# ------------------------------------------------- a weight a row (PR 48)

def dense_weighted(h, w, b, tgt, row_weight, ignore_index=None):
    """``sum_r w_r l_r`` and the rows' ``l_r`` by the dense formula."""
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    rows = -jnp.take_along_axis(
        logp, (tgt.astype(jnp.int32) - 1)[:, None], axis=1)[:, 0]
    if ignore_index is not None:
        rows = jnp.where(tgt.astype(jnp.int32) != ignore_index, rows, 0.0)
    return jnp.sum(rows * row_weight), rows


class TestRowWeight:
    """``fused_lm_head_ce(row_weight=)``: the weighted sum, its gradients
    with respect to the hidden states, the head AND the weights (the rows'
    losses), against the dense formula; without a weight the function is
    the parent's, equation for equation."""

    @pytest.mark.parametrize("chunk,bias,ignore", [
        (7, True, None), (16, False, None), (64, True, 3), (None, False, 5)])
    def test_loss_rows_and_every_gradient(self, chunk, bias, ignore):
        h, w, b, tgt = make_inputs(4)
        b = b if bias else None
        rw = jnp.asarray(np.random.RandomState(5).rand(N).astype(np.float32))

        def fused(h, w, b, rw):
            return fused_lm_head_ce(h, w, b, tgt, chunk=chunk,
                                    size_average=False, ignore_index=ignore,
                                    row_weight=rw, return_rows=True)

        def dense(h, w, b, rw):
            return dense_weighted(h, w, b, tgt, rw, ignore)

        (got, rows), grads = jax.value_and_grad(
            fused, argnums=(0, 1, 2, 3) if bias else (0, 1, 3),
            has_aux=True)(h, w, b, rw)
        (want, want_rows), want_g = jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3) if bias else (0, 1, 3),
            has_aux=True)(h, w, b, rw)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(rows), np.asarray(want_rows),
                                   rtol=1e-5, atol=1e-6)
        for a, e in zip(grads, want_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=2e-5, rtol=1e-4)
        # the weights' gradient IS the rows' losses
        np.testing.assert_allclose(np.asarray(grads[-1]), np.asarray(rows),
                                   rtol=1e-6)

    def test_unit_weights_are_the_unweighted_loss(self):
        h, w, b, tgt = make_inputs(6)
        ones = jnp.ones((N,), jnp.float32)
        for avg in (True, False):
            got = fused_lm_head_ce(h, w, b, tgt, size_average=avg,
                                   ignore_index=2, row_weight=ones)
            want = fused_lm_head_ce(h, w, b, tgt, size_average=avg,
                                    ignore_index=2)
            np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_leading_shapes_and_a_scaled_cotangent(self):
        """(P, B, T) weights over (P, B, T, E) hidden states, as the looped
        decoder's criterion calls it; a cotangent other than 1 scales every
        gradient, the weights' too."""
        rng = np.random.RandomState(7)
        h = jnp.asarray(rng.randn(2, 3, 4, E).astype(np.float32))
        w = jnp.asarray(rng.randn(V, E).astype(np.float32) * 0.3)
        tgt = jnp.asarray(rng.randint(1, V + 1, (2, 3, 4)).astype(np.float32))
        rw = jnp.asarray(rng.rand(2, 3, 4).astype(np.float32))

        def fused(h, w, rw):
            return 2.5 * fused_lm_head_ce(h, w, None, tgt,
                                          size_average=False, row_weight=rw)

        def dense(h, w, rw):
            return 2.5 * dense_weighted(h.reshape(-1, E), w, None,
                                        tgt.reshape(-1), rw.reshape(-1))[0]

        got, want = (jax.grad(f, argnums=(0, 1, 2))(h, w, rw)
                     for f in (fused, dense))
        for a, e in zip(got, want):
            assert a.shape == e.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=2e-5, rtol=1e-4)
        rows = fused_lm_head_ce(h, w, None, tgt, size_average=False,
                                row_weight=rw, return_rows=True)[1]
        assert rows.shape == tgt.shape

    def test_the_rows_carry_no_gradient_of_their_own(self):
        """The rows come back behind ``stop_gradient``: a loss made of them
        alone has no gradient, and they ask for a weight."""
        h, w, b, tgt = make_inputs(8)
        rw = jnp.ones((N,), jnp.float32)
        g = jax.grad(lambda h: jnp.sum(fused_lm_head_ce(
            h, w, b, tgt, row_weight=rw, return_rows=True)[1]))(h)
        assert not np.asarray(g).any()
        with pytest.raises(ValueError, match="row_weight"):
            fused_lm_head_ce(h, w, b, tgt, return_rows=True)

    def test_the_counter_says_which_form_was_traced(self):
        from bigdl_tpu.telemetry import get_registry, instruments
        fam = instruments(get_registry()).lm_head_ce_total
        count = lambda: tuple(fam.labels(form=f).value for f in (
            "weighted_one_pass", "one_pass", "forward_only"))
        h, w, b, tgt = make_inputs(9)
        rw = jnp.ones((N,), jnp.float32)
        before = count()
        jax.make_jaxpr(jax.grad(lambda h: fused_lm_head_ce(
            h, w, b, tgt, row_weight=rw)))(h)
        assert tuple(a - b for a, b in zip(count(), before)) == (1, 0, 0)
        before = count()
        jax.make_jaxpr(lambda h: fused_lm_head_ce(
            h, w, b, tgt, row_weight=rw))(h)
        assert tuple(a - b for a, b in zip(count(), before)) == (0, 0, 1)

    def test_without_a_weight_the_jaxpr_is_the_parents(self):
        """``row_weight=None`` is the call without the keyword: plain and
        under ``grad``, with and without a bias, both trace to the same
        text, no equation of it reads a weight, and the unweighted form is
        what is counted."""
        import re
        from bigdl_tpu.telemetry import get_registry, instruments
        fam = instruments(get_registry()).lm_head_ce_total
        count = lambda: tuple(fam.labels(form=f).value for f in (
            "weighted_one_pass", "one_pass"))
        t = jnp.ones((2, 24))
        h = jnp.zeros((2, 24, 16), jnp.bfloat16)
        w, b = jnp.zeros((40, 16)), jnp.zeros((40,))

        def text(fn, bias):
            return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(
                h, w, bias)))

        def bare(h, w, b):
            return fused_lm_head_ce(h, w, b, t, ignore_index=3)

        def keyed(h, w, b):
            return fused_lm_head_ce(h, w, b, t, ignore_index=3,
                                    row_weight=None, return_rows=False)

        before = count()
        for bias in (None, b):
            assert text(keyed, bias) == text(bare, bias)
            assert text(jax.grad(keyed, argnums=(0, 1)), bias) \
                == text(jax.grad(bare, argnums=(0, 1)), bias)
        assert tuple(a - b for a, b in zip(count(), before)) == (0, 4)
