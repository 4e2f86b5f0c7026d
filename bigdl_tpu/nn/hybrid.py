"""Decoder built from a PATTERN of single-mixer blocks: the hybrid
state-space / attention / mixture-of-experts models (Nemotron-H,
Granite-4-H, Jamba) interleave layers of different kinds, where
``TransformerEncoder`` repeats one block.

A block is residual with ONE mixer, pre-norm unless told otherwise, ``x <-
x + mixer(RMSNorm(x))``, and the pattern string names each block's mixer by
one character: ``M`` a Mamba-2 layer (``nn.Mamba2``), ``E`` a mixture of
experts (``parallel.expert.MoE``), ``*`` causal self-attention
(``nn.MultiHeadAttention``), ``W`` causal self-attention from a SECOND
keyword group (the sliding-window layers of a model that mixes them with
full ones: another window, rotation or head count), ``L`` causal latent
self-attention (``nn.LatentAttention``), ``-`` a dense gated MLP
(``GatedMLP``), ``C`` a double-gated short convolution (``nn.ShortConv``),
``D`` gated delta-rule linear attention (``nn.GatedDeltaNet``),
``R`` a mixture of experts whose ROUTER reads the stream
that entered the PRECEDING block (a layer that routes from its input,
ahead of its attention: ``x <- x + experts(RMSNorm(x); routed by h)``, ``h``
what the attention block before it was given, not normed). The mixers are
built from the keyword groups the caller gives for each kind (``R`` from
the ``E`` blocks' group). With ``post_norm`` a block norms its mixer's
output too, ``x <- x + RMSNorm(mixer(RMSNorm(x)))``, so a layer of an
attention and a feed-forward block holds four norms (the Trinity-Mini
configuration); with ``post_norm`` and ``pre_norm=False`` it norms the
output ALONE, ``x <- x + RMSNorm(mixer(x))``, the block of the OLMo 2
family (the Olmo-Hybrid configuration, kinds ``D*-``). Every other
configuration's blocks are pre-norm.

With ``passes`` P > 1 the stack is LOOPED (a weight-shared,
recurrent-depth decoder, the Ouro configuration): all its blocks run P
times over the SAME parameters, the final norm closes every pass and the
next pass reads the normed stream, ``h_t = norm(stack(h_{t-1}))``.
``pass_streams`` hands on all P normed streams (each goes through the
model's one head in training, ``models.hybrid.build_hybrid_lm(passes=)``);
the module's output is the last.

``MTPModule`` is a multi-token-prediction module over such a stack: a
short second stack fed the main one's stream and the NEXT token's
embedding, whose output goes through the model's own head to predict the
token after next (``models.hybrid.build_hybrid_lm(mtp=...)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import (LatentAttention, MultiHeadAttention,
                                    RMSNorm)
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.remat import MLP_PROJ, block_remat_policy, keep


class GatedMLP(Module):
    """The dense gated feed-forward, ``(silu(x W_gate) * x W_up) W_down``,
    without bias.

    ``TransformerEncoderLayer(activation="swiglu")`` computes the same from
    three ``Linear``s that hang on the LAYER (``linear_gate``, ``linear1``,
    ``linear2``, with the layer's ``bias``): the state-dict interop, the
    tensor-parallel specs and the Qwen builder read them under those names,
    so that layout stays, and a single-mixer block needs its feed-forward
    as a module of its own. The arithmetic is this one line in both."""

    def __init__(self, embed_dim: int, hidden_size: int):
        super().__init__()
        from bigdl_tpu.nn.linear import Linear
        self.gate = Linear(embed_dim, hidden_size, with_bias=False)
        self.up = Linear(embed_dim, hidden_size, with_bias=False)
        self.down = Linear(hidden_size, embed_dim, with_bias=False)

    def update_output(self, input):
        # the three products' outputs are kept across a block's
        # rematerialisation (ops.remat); the down one is read again only by
        # a norm on it (HybridBlock.norm_post)
        with jax.named_scope("mlp"):
            gate = keep(self.gate.forward(input), MLP_PROJ)
            up = keep(self.up.forward(input), MLP_PROJ)
            return keep(self.down.forward(jax.nn.silu(gate) * up), MLP_PROJ)


class HybridBlock(Module):
    """``x + mixer(norm(x))``, or with ``post_norm``
    ``x + norm_post(mixer(norm(x)))``; without ``pre_norm`` there is no
    ``norm`` and the mixer reads ``x`` itself. With ``routed_ahead`` the
    input is a pair ``(x, h)`` and the mixer (an expert layer with
    ``router_input="given"``) is handed ``(norm(x), h)``: its router reads
    ``h`` as it is."""

    def __init__(self, embed_dim: int, mixer: Module, norm_eps: float,
                 post_norm: bool = False, routed_ahead: bool = False,
                 pre_norm: bool = True):
        super().__init__()
        if not (pre_norm or post_norm):
            raise ValueError("a block norms its mixer's input, its output "
                             "or both")
        if pre_norm:
            self.norm = RMSNorm(embed_dim, eps=norm_eps)
        self.mixer = mixer
        self.routed_ahead = routed_ahead
        if post_norm:
            self.norm_post = RMSNorm(embed_dim, eps=norm_eps)

    def update_output(self, input):
        entered = None
        if self.routed_ahead:
            input, entered = input
        y = self.norm.forward(input) if "norm" in self._modules else input
        y = self.mixer.forward((y, entered) if self.routed_ahead else y)
        if "norm_post" in self._modules:
            y = self.norm_post.forward(y)
        return input + y


class HybridDecoder(Module):
    """The stack a pattern string describes (kinds ``ME*W-LRCD``; ``C`` a
    short convolution, ``D`` a gated delta rule), with a final RMSNorm.

    ``mamba``, ``moe``, ``attention``, ``window_attention``,
    ``latent_attention``, ``mlp``, ``short_conv`` and ``delta`` are the
    keyword arguments of ``nn.Mamba2(embed_dim, ...)``, ``MoE(embed_dim,
    ...)``, ``nn.MultiHeadAttention(embed_dim, ..., causal=True)`` for the
    ``*`` and for the ``W`` blocks, ``nn.LatentAttention(embed_dim, ...)``,
    ``GatedMLP(embed_dim, ...)``, ``nn.ShortConv(embed_dim, ...)`` and
    ``nn.GatedDeltaNet(embed_dim, ...)``; a kind the pattern does not use
    needs none.

    ``passes`` P > 1 runs the whole stack P times over the same parameters
    with the final norm between passes (module docstring): the output is
    the last pass's normed stream, ``pass_streams`` gives all P. The loop is
    one ``lax.scan`` body; inside it a block may not write a buffer (none of
    the kinds does in training)."""

    KINDS = "ME*W-LRCD"

    #: as ``TransformerEncoder.remat_blocks``: ``Optimizer.set_remat(
    #: "block")`` sets it, and each block then runs under ``jax.checkpoint``
    #: in training. The backward keeps the block boundaries and what
    #: ``ops.remat.BLOCK_SAVED_NAMES`` lists (the module docstring there has
    #: the table): an attention block's five projection outputs and flash's
    #: ``o`` and ``lse`` (30.8 KB a token at 32 / 4 heads of 128 with a gate
    #: and a second norm, 17.5 KB at 32 / 2 with neither), a latent
    #: attention block's flash ``o`` and ``lse`` alone (8.3 KB at 32 heads
    #: of 128 + 64 over 128: its five low-rank projections run again,
    #: measured no dearer than holding them), a dense block's
    #: gate, up and down outputs (28.7 KB at hidden 6,144 over 2,048), a
    #: Mamba-2 block's in-projection output (20.6 KB at 10,304 wide), a
    #: convolution block's in-projection output (12.3 KB at 3 x 2,048), a
    #: delta-rule block's in-projection output (17.3 KB at 15 heads of 96
    #: and 192), an expert block's routing tables, routed output and its shared
    #: expert's float32 first products (128 B at top-8, 2 bytes a channel,
    #: 4 bytes a hidden unit or 8 for SwiGLU); norms, rotation, gates, the
    #: convolutions, the scan, the delta rule, the router's product and the
    #: shared expert's second product run a second time. An ``R`` block's
    #: checkpoint takes two values, the stream and the one its router
    #: reads, which is a block boundary that is kept anyway
    remat_blocks = False

    #: the LAST name of ``ops.remat.BLOCK_SAVED_NAMES`` that block remat
    #: keeps in this decoder (the tuple is in dropping order, so what is
    #: kept is a leading part of it); None keeps the whole list. A looped
    #: stack holds ``passes`` x the activations over one set of parameters
    #: and may not have room for all of it
    remat_keep_through = None

    def __init__(self, pattern: str, embed_dim: int, mamba=None, moe=None,
                 attention=None, norm_eps: float = 1e-5,
                 window_attention=None, mlp=None, post_norm: bool = False,
                 latent_attention=None, short_conv=None, delta=None,
                 pre_norm: bool = True, passes: int = 1):
        super().__init__()
        if passes < 1:
            raise ValueError(f"passes {passes}: the stack runs at least "
                             f"once")
        self.passes = passes
        bad = set(pattern) - set(self.KINDS)
        if bad or not pattern:
            raise ValueError(f"pattern {pattern!r}: blocks are named by "
                             f"the characters {self.KINDS!r}")
        if pattern[0] == "R":
            raise ValueError(f"pattern {pattern!r}: an 'R' block's router "
                             f"reads the stream that entered the block "
                             f"before it, so it cannot come first")
        self.pattern = pattern
        self.num_layers = len(pattern)
        for i, kind in enumerate(pattern):
            if kind == "M":
                from bigdl_tpu.nn.mamba import Mamba2
                mixer = Mamba2(embed_dim, **mamba)
            elif kind in "ER":
                from bigdl_tpu.parallel.expert import MoE
                mixer = MoE(embed_dim, **moe, **(
                    {"router_input": "given"} if kind == "R" else {}))
            elif kind == "-":
                mixer = GatedMLP(embed_dim, **mlp)
            elif kind == "L":
                mixer = LatentAttention(embed_dim, **latent_attention)
            elif kind == "C":
                from bigdl_tpu.nn.short_conv import ShortConv
                mixer = ShortConv(embed_dim, **(short_conv or {}))
            elif kind == "D":
                from bigdl_tpu.nn.gated_delta_net import GatedDeltaNet
                mixer = GatedDeltaNet(embed_dim, **delta)
            else:
                mixer = MultiHeadAttention(
                    embed_dim, causal=True,
                    **(window_attention if kind == "W" else attention))
            self.add_module(f"layer{i}", HybridBlock(
                embed_dim, mixer, norm_eps, post_norm,
                routed_ahead=kind == "R", pre_norm=pre_norm))
        self.final_norm = RMSNorm(embed_dim, eps=norm_eps)

    def stream(self, input):
        """The residual stream after the last block, before the final
        norm (what a multi-token-prediction module reads)."""
        x = input
        entered = None      # what the preceding block was given
        ckpt = self.remat_blocks and self.training
        for i, kind in enumerate(self.pattern):
            layer = self._modules[f"layer{i}"]
            # an ``R`` block is given the stream and what its router reads
            args = (x, entered) if kind == "R" else (x,)

            def run(*a, _l=layer):
                return _l.forward(a[0] if len(a) == 1 else a)

            if ckpt:
                # kept by name, whatever the block is made of (ops.remat)
                run = jax.checkpoint(run, policy=block_remat_policy(
                    self.remat_keep_through))
            entered, x = x, run(*args)
        return x

    def pass_streams(self, input):
        """The normed stream after each pass, stacked: (passes, B, T, E).
        One pass is ``final_norm(stream(.))`` and the next reads its
        output. The loop is ONE traced body under ``lax.scan``: the
        program holds one copy of the stack and of its kernel calls, and
        the cotangents of the shared parameters are summed over the passes
        by the scan's transpose, in the parameters' own dtype."""
        from bigdl_tpu.telemetry import get_registry, instruments
        # trace-time count, as bigdl_ssd_scan_total
        instruments(get_registry()).decoder_passes_total.inc()

        def one_pass(h, _):
            h = self.final_norm.forward(self.stream(h))
            return h, h

        return jax.lax.scan(one_pass, input, None, length=self.passes)[1]

    def update_output(self, input):
        if self.passes == 1:
            return self.final_norm.forward(self.stream(input))
        return self.pass_streams(input)[-1]

    def __repr__(self):
        return f"HybridDecoder({self.pattern!r})"


class MTPModule(Module):
    """One multi-token-prediction module (DeepSeek-V3, depth 1). From the
    embedded tokens ``e`` (B, T, E) and the main stack's stream ``h``
    (B, T, E; its last block's output, before the final norm)::

        z_i = [RMSNorm_e(e_{i+1}) ; RMSNorm_h(h_i)] W_eh      (2E -> E)

    then ``stack`` (a short ``HybridDecoder``: blocks of the main kind and
    a final norm, which is the norm in front of the SHARED head) gives the
    stream whose position i predicts token i + 2. The next token's
    embedding is the main embedding's own output one position on, so the
    lookup table takes gradient from both uses; the last position has no
    next token and gets zeros: it is the one the criterion leaves out
    (``FusedLMHeadCriterion``), and under a causal mixer no other position
    sees it. ``loss_weight`` rides with the stream to the criterion, which
    returns ``L_main + loss_weight * L_mtp``. Everything here runs under
    the scope ``mtp``."""

    def __init__(self, embed_dim: int, stack: HybridDecoder, norm_eps: float,
                 loss_weight: float):
        super().__init__()
        from bigdl_tpu.nn.linear import Linear
        self.loss_weight = loss_weight
        self.norm_embed = RMSNorm(embed_dim, eps=norm_eps)
        self.norm_hidden = RMSNorm(embed_dim, eps=norm_eps)
        self.proj = Linear(2 * embed_dim, embed_dim, with_bias=False)
        self.stack = stack

    def update_output(self, input):
        from bigdl_tpu.telemetry import get_registry, instruments
        # trace-time count, as bigdl_ssd_scan_total
        instruments(get_registry()).mtp_modules_total.inc()
        embedded, stream = input
        with jax.named_scope("mtp"):
            nxt = jnp.concatenate(
                [embedded[:, 1:], jnp.zeros_like(embedded[:, :1])], axis=1)
            z = self.proj.forward(jnp.concatenate(
                [self.norm_embed.forward(nxt),
                 self.norm_hidden.forward(stream)], axis=-1))
            return self.stack.forward(z)
