"""Expert parallelism: distributed mixture-of-experts.

The reference's ``MixtureTable`` (``nn/MixtureTable.scala:1``) is a
single-node MoE *gating container* — SURVEY §2.5 records "Expert
parallelism: ABSENT". ``MoE`` is its distributed descendant, built the
GShard/Switch way for TPU:

- top-k softmax gating with capacity limiting;
- ``dispatch="sort"`` (the default), the capacity path: ONE stable
  argsort of the round-major token→expert picks — capacity slots fall
  out of segment offsets (rank within the expert's sorted run), tokens
  GATHER into the (expert, capacity, d) buffers, and the combine reads
  back through the same indices. Static shapes, O(E·C·D) memory, and no
  (T, E)-wide cumsum chains or scatter traffic on the hot path. The dense
  GShard (T, E, C) formulation of the same routing is the tests'
  reference (``tests/test_expert_parallel.py``), not an option here;
- expert FFN weights STACKED on a leading expert axis; under expert
  parallelism those leaves are sharded ``P('expert', ...)`` and GSPMD turns
  the dispatch einsums into all_to_alls over the mesh ``expert`` axis —
  layout-as-strategy, same arrays as single-chip execution
  (``expert_param_specs``).
- the Switch load-balance auxiliary loss is folded into the backward pass
  via ``inject_loss`` (the autodiff analogue of the reference
  ``L1Penalty``'s gradient-injection trick), so training loops need no
  MoE-specific loss plumbing.

Tokens over capacity are dropped (their combine weight is zero and they
pass through the residual connection unchanged when used inside a
transformer block).

``dispatch="held"`` is the dropless layer of an expert-parallel
deployment seen from ONE of its chips: the router scores all
``n_experts`` by a sigmoid, picks the top k of score + selection bias,
renormalises the picked scores and scales them by ``route_scale`` (no
auxiliary loss: ``aux_loss_weight`` is not read); with
``score="softmax_picked"`` the scores are the router's logits and the
weights a softmax over the k picked ones (the same as a softmax over all
of them renormalised over the picks). With ``router_input="given"`` the
layer's input is a pair ``(x, r)``: the experts read ``x`` and the router
``r``, a stream of the same tokens that the model computed EARLIER (the
layer's input, ahead of its attention), so the routing waits on nothing
the experts' input waits on. The layer holds the
experts whose ids ``held`` lists and computes the part of the result that
those give, plus an always-on shared expert (``shared_hidden``); what the
absent experts would add is the other chips' to compute and is not stood
in for. The local picks are sorted by expert and the held experts run as
one grouped product over the sorted rows (``_grouped_rows``): no capacity,
no dropped token, and the work follows the rows that really landed here,
not the static bound ``T * min(k, len(held))``. Between the picks and the
tables that product reads there is no per-pick gather or scatter (on a TPU
XLA runs those an element at a time, 9 ns each): the picked scores, each
pick's local id and the rows an expert come from comparing and selecting,
and ONE stable sort by local id carries each pick's index and its combine
weight along as payloads (PERF.md section 6, PR 42). On a TPU, for experts
without biases whose widths are whole lane tiles, the products are the
Mosaic kernels of ``ops/grouped_matmul.py`` (``takes_kernel``): row tiles
that pad no run, an expert's matrices read once a pass, its float32
gradient tile kept in VMEM over all its rows, the activation and the
scatter-add to the tokens' rows as the kernels' epilogues; everywhere else
XLA loops over row blocks of ``_BLOCK_ROWS`` (an expert's run rounded up to
whole blocks). ``bigdl_moe_grouped_total{form}`` says which a compiled
layer holds. With ``pick_rows`` the held layer takes each
token's k experts from a table by the token's ID (``pick_table``: a fixed
hash layer, Roller et al., arXiv:2106.04426, filled by whoever builds the
model) and only their combine weights from the live scores: its work a
step is then a function of the batch's ids alone, however the stream
moves as it trains. The model that owns the layer says which ids its
stream carries (``token_ids``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bigdl_tpu.nn import initialization as init
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops import grouped_matmul
from bigdl_tpu.ops.scopes import under_scope
from bigdl_tpu.ops.remat import (MOE_ROUTE_TABLES, MOE_ROUTED_OUT,
                                 MOE_SHARED_HID, keep)
from bigdl_tpu.parallel.mesh import EXPERT_AXIS


#: the token ids of the stream being traced, innermost last (``token_ids``)
_TOKEN_IDS = []


@contextlib.contextmanager
def token_ids(ids):
    """While a model runs its stack: ``ids`` (any shape, 1-based, as the
    lookup table takes them) are the tokens at the stream's positions, for
    the expert layers that pick by table (``MoE(pick_rows=)``). Read at
    trace time; a layer inside ``jax.checkpoint`` closes over them."""
    _TOKEN_IDS.append(ids)
    try:
        yield
    finally:
        _TOKEN_IDS.pop()


@jax.custom_vjp
def inject_loss(y, aux):
    """Identity on ``y`` that adds ``aux`` to the total loss through the
    backward pass (cotangent 1.0 regardless of downstream), so auxiliary
    losses compose without touching the training loop."""
    return y


def _inject_fwd(y, aux):
    return y, None


def _inject_bwd(_, g):
    return g, jnp.ones(())


inject_loss.defvjp(_inject_fwd, _inject_bwd)


@jax.custom_jvp
def _in_order(weight, order, sorted_weight):
    """``weight[order]`` where a sort has already carried ``weight`` along
    as its payload (``sorted_weight``): the value costs no gather, and the
    cotangent goes back to pick order through ``order``, a row once."""
    return sorted_weight


@_in_order.defjvp
def _in_order_jvp(primals, tangents):
    _, order, sorted_weight = primals
    return sorted_weight, tangents[0].at[order].get(
        unique_indices=True, mode="promise_in_bounds")


def _relu2(hid):
    return jnp.square(jax.nn.relu(hid))


def _swiglu(hid, gate):
    return jax.nn.silu(gate) * hid


def _reglu(hid, gate):
    return jax.nn.relu(gate) * hid


#: an expert's activation by name, on its float32 first products
_ACTIVATIONS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "relu2": _relu2,
                "swiglu": _swiglu, "reglu": _reglu}
#: those of the gated form: a gate matrix ``wg`` beside ``w1``
_GATED = ("swiglu", "reglu")
#: the held router's score rules (``MoE._route``)
_SCORES = ("sigmoid", "softmax_picked")


class MoE(Module):
    """Top-k gated mixture of expert FFNs (distributed ``MixtureTable``).

    Input (..., D) — leading axes are flattened into a token axis. Each
    expert is a two-layer FFN D -> H -> D, or with ``activation="swiglu"``
    / ``"reglu"`` (``dispatch="held"`` only) the gated form of three
    matrices, ``(silu(x W_g) * x W_1) W_2`` / ``(relu(x W_g) * x W_1)
    W_2``, the shared expert alike. ``held``, ``shared_hidden``,
    ``route_scale``, ``train_router``, ``score`` and ``router_input``
    belong to ``dispatch="held"`` (module docstring).
    ``train_router=False`` takes the combine weights as constants: a chip that holds a share of the
    experts and exchanges nothing sees only that share of the router's
    gradient, and applied alone it trains the router TOWARD the held
    experts; the router then gets no gradient (nor does the layer's input
    through it) and keeps its picks. ``pick_rows`` (the vocabulary's size;
    ``dispatch="held"``) adds the buffer ``pick_table`` (pick_rows, k) and
    picks from it by token id (module docstring); it is zeros until filled.
    ``renorm_eps`` stands under the sum of the picked sigmoid scores
    (1e-6 in the families whose class writes one).
    """

    def __init__(self, input_size: int, hidden_size: int, n_experts: int,
                 k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", aux_loss_weight: float = 1e-2,
                 dispatch: str = "sort", held=None, bias: bool = True,
                 shared_hidden: int = 0, route_scale: float = 1.0,
                 train_router: bool = True, pick_rows: int = 0,
                 score: str = "sigmoid", router_input: str = "own",
                 renorm_eps: float = 1e-20):
        super().__init__()
        if dispatch not in ("sort", "held"):
            raise ValueError(f"dispatch must be 'sort' or 'held', got "
                             f"{dispatch!r}")
        if activation not in _ACTIVATIONS:
            raise ValueError(f"unknown expert activation {activation!r}")
        if activation in _GATED and (dispatch != "held" or bias):
            raise ValueError(f"{activation} experts (a gate matrix beside "
                             f"w1, no bias) belong to dispatch='held'")
        if score not in _SCORES:
            raise ValueError(f"score must be one of {_SCORES}, got "
                             f"{score!r}")
        if router_input not in ("own", "given"):
            raise ValueError(f"router_input must be 'own' or 'given', got "
                             f"{router_input!r}")
        if dispatch != "held" and (held is not None or shared_hidden
                                   or route_scale != 1.0
                                   or not train_router or pick_rows
                                   or score != "sigmoid"
                                   or router_input != "own"):
            raise ValueError("held, shared_hidden, route_scale, pick_rows, "
                             "train_router, score and router_input belong "
                             "to dispatch='held' (the capacity paths route "
                             "by softmax over experts that are all here)")
        # ids of the experts whose weights live here (all of them unless
        # dispatch='held' names a share); the router is n_experts wide
        # either way
        self.held = tuple(range(n_experts)) if held is None \
            else tuple(int(i) for i in held)
        if (len(set(self.held)) != len(self.held) or not self.held
                or min(self.held) < 0 or max(self.held) >= n_experts):
            raise ValueError(f"held must list distinct expert ids in "
                             f"[0, {n_experts}), got {held!r}")
        self.bias = bias
        self.shared_hidden = shared_hidden
        self.route_scale = route_scale
        self.train_router = train_router
        self.score = score
        self.router_input = router_input
        self.renorm_eps = renorm_eps
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.n_experts = n_experts
        self.k = min(k, n_experts)
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.aux_loss_weight = aux_loss_weight
        self.dispatch = dispatch
        d, h, e = input_size, hidden_size, n_experts
        n = len(self.held)
        self.register_parameter("gate_weight", init.xavier((d, e), d, e))
        if dispatch == "held":
            # selection bias: moves which experts are picked, never their
            # weights; a buffer (no gradient), set by a balancing rule
            self.register_buffer("select_bias", init.zeros((e,)))
        self.pick_rows = pick_rows
        if pick_rows:
            # expert ids as float32 (exact to 2^24), like every buffer
            self.register_buffer("pick_table",
                                 init.zeros((pick_rows, self.k)))
        self.register_parameter(
            "w1", np.stack([init.xavier((d, h), d, h) for _ in range(n)]))
        if activation in _GATED:
            self.register_parameter(
                "wg", np.stack([init.xavier((d, h), d, h) for _ in range(n)]))
        if bias:
            self.register_parameter("b1", init.zeros((n, h)))
        self.register_parameter(
            "w2", np.stack([init.xavier((h, d), h, d) for _ in range(n)]))
        if bias:
            self.register_parameter("b2", init.zeros((n, d)))
        if shared_hidden:
            # an always-on expert of its own width beside the routed ones
            hs = shared_hidden
            self.register_parameter("shared_w1", init.xavier((d, hs), d, hs))
            if activation in _GATED:
                self.register_parameter("shared_wg",
                                        init.xavier((d, hs), d, hs))
            self.register_parameter("shared_w2", init.xavier((hs, d), hs, d))
            if bias:
                self.register_parameter("shared_b1", init.zeros((hs,)))
                self.register_parameter("shared_b2", init.zeros((d,)))

    def _act(self, x):
        return _ACTIVATIONS[self.activation](x)

    @property
    def _activate(self):
        """An expert's activation on its float32 first products, elementwise:
        ``(hid, gate)`` from ``w1`` (its bias added) and ``wg`` for the
        gated form, ``(hid,)`` otherwise. A module-level function, one an
        activation's name: the grouped kernels run it as their epilogue and
        the layers of a model share their compiled calls by it."""
        return _ACTIVATIONS[self.activation]

    def _hidden(self, w, x, tag=lambda v: v):
        """An expert's activations on its rows, before its output matrix:
        ``w`` holds that expert's matrices under the stacked leaves' names
        (``shared_`` stripped). ``tag`` is applied to the float32 first
        products (the shared expert's are kept across block remat)."""
        f32 = jnp.float32
        cd = x.dtype
        first = [tag(jnp.dot(x, w[k].astype(cd), preferred_element_type=f32))
                 for k in ("w1", "wg") if k in w]
        if "b1" in w:
            first[0] = first[0] + w["b1"].astype(f32)
        return self._activate(*first).astype(cd)

    def _route(self, x):
        """The held layer's router: (expert ids (T, k), combine weights
        (T, k) float32) of every token over ALL n_experts. ``score``
        ``"sigmoid"``: the picked sigmoid scores over their sum;
        ``"softmax_picked"``: a softmax over the picked logits."""
        scores = jnp.dot(x, self.gate_weight.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if self.score == "sigmoid":
            scores = jax.nn.sigmoid(scores)
        if not self.train_router:
            scores = jax.lax.stop_gradient(scores)
        if self.pick_rows:
            if not _TOKEN_IDS:
                raise ValueError("MoE(pick_rows=) picks by token id: the "
                                 "model runs its stack under token_ids(ids)")
            ids = _TOKEN_IDS[-1].reshape(-1).astype(jnp.int32) - 1
            picked = jax.lax.stop_gradient(
                self.pick_table)[ids].astype(jnp.int32)
        else:
            _, picked = jax.lax.top_k(
                scores + jax.lax.stop_gradient(
                    self.select_bias.astype(jnp.float32)), self.k)
        # kept across a block's rematerialisation (ops.remat)
        picked = keep(picked, MOE_ROUTE_TABLES)
        # each pick's score by compare-and-select over the experts: one
        # pass over ``scores`` (its transpose a dense select), no gather
        experts = jnp.arange(self.n_experts, dtype=jnp.int32)
        w = keep(jnp.stack(
            [jnp.sum(jnp.where(picked[:, j:j + 1] == experts, scores, 0.0),
                     axis=-1) for j in range(self.k)], axis=-1),
            MOE_ROUTE_TABLES)
        if self.score == "sigmoid":
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + self.renorm_eps)
        else:
            w = jax.nn.softmax(w, axis=-1)
        return picked, w * self.route_scale

    def _held_forward(self, input):
        """The dropless layer over the experts held here."""
        routed_from = None
        if self.router_input == "given":
            input, routed_from = input
        orig_shape = input.shape
        d, e, k = self.input_size, self.n_experts, self.k
        x = input.reshape(-1, d)
        t = x.shape[0]
        n = len(self.held)
        # a router that reads a stream from ahead of the layer's mixer has
        # a scope of its own: the step's partition charges its product,
        # top-k, sort and count apart from a router that waits on its block
        with jax.named_scope("moe_route_ahead") \
                if self.router_input == "given" \
                else jax.named_scope("moe_route"):
            picked, weight = self._route(
                x if routed_from is None else routed_from.reshape(-1, d))
            # local id of each pick: position of its expert in ``held``,
            # n where the expert lives on another chip; the counts an
            # expert and the ids by comparing, as the picked scores
            flat = picked.reshape(-1)                            # (T*k,)
            if self.held == tuple(range(e)):
                lid = flat
            else:
                lid = jnp.full(flat.shape, n, jnp.int32)
                for j, h in enumerate(self.held):
                    lid = jnp.where(flat == h, j, lid)
            counts = keep(jnp.sum(
                lid == jnp.arange(n, dtype=jnp.int32)[:, None], axis=1,
                dtype=jnp.int32), MOE_ROUTE_TABLES)
            rows = t * min(k, n)            # the picks that CAN land here
            # ONE stable sort by local id carries each pick's index and
            # its combine weight along: the landed picks first, grouped by
            # expert, in pick order. The routing's tables are kept across
            # a block's rematerialisation (``_route`` keeps the picks and
            # their scores): the top-k, the sort and the count run once;
            # the router's product and the weights' arithmetic twice
            weight = weight.reshape(-1)
            _, order, gate = jax.lax.sort(
                (lid, jnp.arange(t * k, dtype=jnp.int32),
                 jax.lax.stop_gradient(weight)), num_keys=1)
            order = keep(order[:rows], MOE_ROUTE_TABLES)
            gate = keep(_in_order(weight, order, gate[:rows]),
                        MOE_ROUTE_TABLES)
            tok = order // k
        with jax.named_scope("moe_experts"):
            routed = {p: v for p, v in self._parameters.items()
                      if p in ("w1", "wg", "b1", "w2", "b2")}
            y = _grouped_rows(self._hidden, self._activate, routed, x, tok,
                              gate, counts)
            # kept across a block's rematerialisation
            # (ops.remat.block_remat_policy): the loop runs once forward
            y = keep(y.astype(input.dtype), MOE_ROUTED_OUT) \
                .astype(jnp.float32)
        if self.shared_hidden:
            with jax.named_scope("moe_shared"):
                shared = {p[len("shared_"):]: v
                          for p, v in self._parameters.items()
                          if p.startswith("shared_")}
                hid = self._hidden(shared, x,
                                   lambda v: keep(v, MOE_SHARED_HID))
                y = y + _project(shared, hid).astype(jnp.float32)
        return y.astype(input.dtype).reshape(orig_shape)

    def update_output(self, input):
        from bigdl_tpu.telemetry import get_registry, instruments
        ins = instruments(get_registry())
        # trace-time counts (as bigdl_ssd_scan_total): which path each
        # compiled MoE forward uses
        ins.moe_dispatch_total.labels(path=self.dispatch).inc()
        if self.dispatch == "held":
            ins.moe_router_total.labels(score=self.score,
                                        input=self.router_input).inc()
            return self._held_forward(input)
        orig_shape = input.shape
        d, e, k = self.input_size, self.n_experts, self.k
        x = input.reshape(-1, d)
        t = x.shape[0]
        capacity = max(1, int(np.ceil(t / e * self.capacity_factor * k)))
        capacity = min(capacity, t)

        logits = x @ self.gate_weight                      # (T, E)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

        # Iterative top-k routing: round j picks each token's best expert
        # among those it has not picked yet.
        masked = probs
        topk_mask = jnp.zeros_like(probs)
        experts, gates = [], []         # a round's pick (T,) and its gate
        for _ in range(k):
            pick = jnp.argmax(masked, axis=-1)             # (T,)
            onehot = jax.nn.one_hot(pick, e, dtype=jnp.float32)
            topk_mask = topk_mask + onehot
            experts.append(pick)
            gates.append(jnp.sum(probs * onehot, axis=-1))
            masked = masked * (1.0 - onehot)

        # Slot assignment: flatten the picks round-major (flat index
        # j*T + t) and stable-argsort by expert. A pick's rank within its
        # expert's sorted run IS its capacity slot: earlier rounds and,
        # within a round, earlier tokens are served first, and a pick
        # whose rank is past the capacity is dropped.
        kt = k * t
        expert_flat = jnp.concatenate(experts)
        order = jnp.argsort(expert_flat, stable=True)       # (kT,)
        counts = jnp.bincount(expert_flat, length=e)        # (E,)
        offsets = (jnp.cumsum(counts) - counts).astype(jnp.int32)
        # inverse permutation: sorted position of each flat pick
        inv = jnp.zeros((kt,), jnp.int32).at[order].set(
            jnp.arange(kt, dtype=jnp.int32))
        slot_flat = inv - offsets[expert_flat]              # rank in expert
        keep_flat = slot_flat < capacity
        # each round's slots and gate weights, (k, T); drops weigh 0
        slots = jnp.where(keep_flat, slot_flat, 0).reshape(k, t)
        weights = (jnp.concatenate(gates) * keep_flat).reshape(k, t)

        # Renormalise the k kept gate weights to sum 1 per token, then
        # rescale by the FULL top-k probability mass (drops included) —
        # GShard combine semantics.
        denom = jnp.sum(weights, axis=0)                   # (T,)
        scale = jnp.sum(probs * topk_mask, axis=-1)        # (T,)
        coef = scale / jnp.maximum(denom, 1e-9)

        # Dispatch + expert matmuls run in the COMPUTE dtype (bf16 under
        # the training policy: the MXU's native rate). Gating/combine
        # coefficients stay f32.
        cd = input.dtype
        # Pure-gather dispatch: expert e's capacity row c holds the token
        # of its c-th sorted pick (exactly the pick that got slot c),
        # zero-masked past the expert's real count. XLA lowers this to
        # gathers, and under EP sharding the gather feeding the sharded
        # expert einsum becomes the exchange over the expert axis.
        token_flat = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
        sorted_tokens = token_flat[order]                   # (kT,)
        src = offsets[:, None] + jnp.arange(capacity,
                                            dtype=jnp.int32)[None, :]
        valid = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                 < jnp.minimum(counts, capacity)[:, None])  # (E, C)
        gathered = sorted_tokens[jnp.clip(src, 0, kt - 1)]  # (E, C)
        xe = jnp.where(valid[:, :, None], x[gathered], 0).astype(cd)

        hdn = jnp.einsum("ecd,edh->ech", xe, self.w1.astype(cd))
        if self.bias:
            hdn = hdn + self.b1.astype(cd)[:, None, :]
        hdn = self._act(hdn)
        ye = jnp.einsum("ech,ehd->ecd", hdn, self.w2.astype(cd))
        if self.bias:
            ye = ye + self.b2.astype(cd)[:, None, :]

        # combine by (expert, slot) gather-back
        y = jnp.zeros((t, d), jnp.float32)
        for pick, slot, w in zip(experts, slots, weights):
            y = y + (w * coef)[:, None] * ye[pick, slot].astype(
                jnp.float32)
        y = y.astype(input.dtype)

        if self.aux_loss_weight and self.training:
            # Switch-style load balance: E * sum_e f_e * p_e.
            frac = jnp.mean(topk_mask / k, axis=0)          # tokens per expert
            mean_p = jnp.mean(probs, axis=0)
            aux = e * jnp.sum(frac * mean_p) * self.aux_loss_weight
            y = inject_loss(y, aux)
        return y.reshape(orig_shape)

    def __repr__(self):
        held = "" if len(self.held) == self.n_experts \
            else f", held={len(self.held)}"
        return (f"MoE({self.input_size}->{self.hidden_size}, "
                f"experts={self.n_experts}{held}, k={self.k})")


#: rows of one block of the XLA form's loops (below), and of one trip of
#: the kernel form's gather and scatter loops. An XLA ``while`` keeps
#: nothing on chip between trips, so in the XLA form a block is one pass
#: over its expert's matrices, one float32 read-modify-write of their
#: gradients and scatter-adds of its rows, and an expert's last block is
#: padded past its run's end with rows of pick weight zero. How 512 was
#: found: PERF.md section 6, PRs 25 and 35.
_BLOCK_ROWS = 512


def takes_kernel(backend, weights, x) -> bool:
    """The form rule, by what the layer can see: the Mosaic kernels of
    ``ops/grouped_matmul.py`` on a TPU for experts without biases whose two
    widths are whole 128-lane tiles in a dtype the MXU takes; the XLA loops
    everywhere else (a CPU, the biased gelu / relu experts of the tests, a
    width off the lanes: the kernels take 1,856 = 14.5 x 128 as
    whole-dimension blocks and halve the scope's time there, but XLA then
    lays the float32 leaves of that width out transposed and copies them
    in and out of every step, which costs the step what the kernels gave:
    PERF.md section 6, PR 35)."""
    d, h = weights["w1"].shape[1:]
    return (backend == "tpu" and "b1" not in weights and "b2" not in weights
            and d % 128 == 0 and h % 128 == 0
            and x.dtype in (jnp.bfloat16, jnp.float32))


def _block_table(counts, block, n_blocks_max):
    """For block i of the sorted rows: (expert, first row, end of that
    expert's run), and how many blocks there are. ``counts`` (n,) rows an
    expert; runs lie back to back in expert order."""
    offsets = jnp.cumsum(counts) - counts
    per = (counts + block - 1) // block
    last = jnp.cumsum(per)
    i = jnp.arange(n_blocks_max, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(i[:, None] >= last[None, :], axis=1),
                    counts.shape[0] - 1).astype(jnp.int32)
    start = offsets[e] + (i - (last[e] - per[e])) * block
    return e, start.astype(jnp.int32), (offsets + counts)[e], last[-1]


def _project(w, hid):
    """An expert's output matrix (and bias) on its activations."""
    out = jnp.dot(hid, w["w2"].astype(hid.dtype),
                  preferred_element_type=jnp.float32)
    if "b2" in w:
        out = out + w["b2"].astype(jnp.float32)
    return out.astype(hid.dtype)


def _grouped_rows(hidden, activate, weights, x, tok, gate, counts):
    """sum over the sorted picks r of ``gate[r] * expert_r(x[tok[r]])``
    scattered to row ``tok[r]`` -> (T, d) float32, an expert being
    ``_project(w, hidden(w, x))`` and ``activate`` the elementwise part of
    ``hidden`` on its float32 first products.

    ``tok``/``gate`` (R,) hold the picks sorted by held expert, expert e's
    run ``counts[e]`` long; entries past the runs are ignored. Two forms of
    the one sum (``takes_kernel``; counted a trace in
    ``bigdl_moe_grouped_total``), both with work that follows the rows that
    landed and not R, both with float32 accumulation, activations rounded
    to the compute dtype before the output matrix and weight gradients
    summed in float32 over all of an expert's rows:

    - ``kernel``: the products run in the kernels of
      ``ops/grouped_matmul.py`` over the sorted rows as they lie (row tiles
      of ``grouped_matmul.ROW_TILE`` that pad no run), ``activate`` and its
      derivative as their epilogues (``_grouped_kernel``,
      ``_kernel_backward``), the pick weight and the scatter-add to the
      tokens' rows as the output stages' (an expert's output and a row's
      gradient reach their sums in float32, not rounded to the compute
      dtype first, which is what XLA makes of the other form on the TPU:
      it elides a rounding that is widened again at once); XLA ``while``
      loops of a dynamic trip count only gather the landed rows.
    - ``xla``: a loop whose trip count is the number of ``_BLOCK_ROWS``
      blocks that hold real rows (at most one partial block an expert: the
      rows of a block past its run's end are multiplied with the rest and
      weigh zero). The backward is a second such loop: it recomputes a
      block's activations, takes ``jax.vjp`` of ``hidden`` on them (so any
      activation or bias differentiates) and the linear output stage by
      hand (one product gives both the pick weights' gradient and the
      activations')."""
    from bigdl_tpu.telemetry import get_registry, instruments
    kernel = takes_kernel(jax.default_backend(), weights, x)
    # trace-time count, as bigdl_ssd_scan_total: which form a compiled
    # held layer's grouped products took
    instruments(get_registry()).moe_grouped_total.labels(
        form="kernel" if kernel else "xla").inc()
    rows = tok.shape[0]
    if kernel:
        tile = min(grouped_matmul.ROW_TILE, -(-rows // 8) * 8)
        # a trip of the gather and scatter loops: whole row tiles
        block = min(max(_BLOCK_ROWS // tile, 1), -(-rows // tile)) * tile
        # the kernels read ``tok`` in index blocks of their own
        whole = grouped_matmul.INDEX_BLOCK
        pad = -rows % (math.lcm(block, whole) if rows > whole else block)
    else:
        block = min(_BLOCK_ROWS, -(-rows // 8) * 8)
        n_max = rows // block + counts.shape[0]
        pad = n_max * block - rows
    tok = jnp.pad(tok, (0, pad))
    gate = jnp.pad(gate, (0, pad))
    if kernel:
        return _grouped_kernel(activate, block, weights, x, gate, tok, counts)
    return _grouped(hidden, block, n_max, weights, x, gate, tok, counts)


def _block(i, table, block, tok, gate, weights):
    """Block i: its expert, first row, which rows are the expert's own,
    their tokens and pick weights (zero past the run's end), the expert's
    matrices."""
    e, start, end, _ = table
    valid = start[i] + jnp.arange(block, dtype=jnp.int32) < end[i]
    t_b = jax.lax.dynamic_slice(tok, (start[i],), (block,))
    g_b = jnp.where(valid, jax.lax.dynamic_slice(gate, (start[i],),
                                                 (block,)), 0.0)
    w_e = {k: jax.lax.dynamic_index_in_dim(v, e[i], keepdims=False)
           for k, v in weights.items()}
    return e[i], start[i], valid, t_b, g_b, w_e


def _grouped_fwd_loop(hidden, block, n_max, weights, x, gate, tok, counts):
    table = _block_table(counts, block, n_max)

    def body(i, out):
        _, _, _, t_b, g_b, w_e = _block(i, table, block, tok, gate, weights)
        y_b = _project(w_e, hidden(w_e, x[t_b])).astype(jnp.float32)
        return out.at[t_b].add(g_b[:, None] * y_b)

    return jax.lax.fori_loop(0, table[3], body,
                             jnp.zeros(x.shape, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _grouped(hidden, block, n_max, weights, x, gate, tok, counts):
    return _grouped_fwd_loop(hidden, block, n_max, weights, x, gate, tok,
                             counts)


def _grouped_fwd(hidden, block, n_max, weights, x, gate, tok, counts):
    out = _grouped_fwd_loop(hidden, block, n_max, weights, x, gate, tok,
                            counts)
    return out, (weights, x, gate, tok, counts)


@under_scope("moe_experts")
def _grouped_bwd(hidden, block, n_max, res, dout):
    weights, x, gate, tok, counts = res
    return _grouped_bwd_loop(hidden, block, n_max, weights, x, gate, tok,
                             counts, dout)


def _grouped_bwd_loop(hidden, block, n_max, weights, x, gate, tok, counts,
                      dout):
    table = _block_table(counts, block, n_max)
    f32 = jnp.float32
    first = [k for k in weights if k not in ("w2", "b2")]
    has_b2 = "b2" in weights

    def body(i, carry):
        dw, dx, dgate = carry
        e, start, valid, t_b, g_b, w_e = _block(i, table, block, tok, gate,
                                                weights)
        hid, vjp = jax.vjp(hidden, {k: w_e[k] for k in first}, x[t_b])
        dy_b = dout[t_b].astype(f32)
        # y = hid @ w2 (+ b2), out += g * y: dy @ w2^T serves both d(g)
        # = <dy, y> = <dy @ w2^T, hid> (+ <dy, b2>) and d(hid) = g * it
        back = jnp.dot(dy_b.astype(hid.dtype), w_e["w2"].astype(hid.dtype).T,
                       preferred_element_type=f32)
        dg_b = jnp.sum(back * hid.astype(f32), axis=-1)
        gdy = (g_b[:, None] * dy_b).astype(hid.dtype)
        add = {"w2": jnp.dot(hid.T, gdy, preferred_element_type=f32)}
        if has_b2:
            dg_b = dg_b + jnp.sum(dy_b * w_e["b2"].astype(f32), axis=-1)
            add["b2"] = jnp.sum(gdy.astype(f32), axis=0)
        d_first, dx_b = vjp((g_b[:, None] * back).astype(hid.dtype))
        add.update(d_first)
        dw = {k: dw[k].at[e].add(add[k].astype(f32)) for k in dw}
        dx = dx.at[t_b].add(dx_b.astype(f32))
        dgate = jax.lax.dynamic_update_slice(
            dgate, jax.lax.dynamic_slice(dgate, (start,), (block,))
            + jnp.where(valid, dg_b, 0.0), (start,))
        return dw, dx, dgate

    dw, dx, dgate = jax.lax.fori_loop(
        0, table[3], body,
        ({k: jnp.zeros(v.shape, f32) for k, v in weights.items()},
         jnp.zeros(x.shape, f32), jnp.zeros(gate.shape, f32)))
    return ({k: dw[k].astype(v.dtype) for k, v in weights.items()},
            dx.astype(x.dtype), dgate.astype(gate.dtype), None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


# ------------------------------------------------------- the kernel form

def _landed_rows(src, tok, landed, block):
    """``src[tok]`` over the first ``landed`` rows of ``tok`` (rounded up to
    whole blocks) -> (R, C); what lies past them is not written."""
    def body(i, buf):
        t_b = jax.lax.dynamic_slice(tok, (i * block,), (block,))
        return jax.lax.dynamic_update_slice(buf, src[t_b], (i * block, 0))

    return jax.lax.fori_loop(
        0, (landed + block - 1) // block, body,
        jax.lax.empty((tok.shape[0], src.shape[1]), src.dtype))


# The kernels' epilogues: equal by value, so that the expert blocks of a
# model share one compiled call a stage (``grouped_matmul.grouped_products``)

@dataclasses.dataclass(frozen=True)
class _Activated:
    """The forward's first stage: the activation of the first products."""
    activate: object

    def __call__(self, prods, cols):
        return [self.activate(*prods)]


def _weighted(prods, cols):
    """The forward's output stage: pick weight x (hid @ w2), float32."""
    return [cols[0] * prods[0]]


@dataclasses.dataclass(frozen=True)
class _Backward:
    """The backward's fused stage, from the first products and ``dy @
    w2^T``: the activations again (rounded to ``cd``), the first products'
    gradients through the activation's derivative at pick weight x ``dy @
    w2^T`` (rounded as the XLA form rounds them), and the rows whose sums
    are the pick weights' gradient ``<dy @ w2^T, hid>``."""
    activate: object
    cd: object

    def __call__(self, prods, cols):
        f32 = jnp.float32
        hid, vjp = jax.vjp(self.activate, *prods[:-1])
        hid = hid.astype(self.cd)
        d_first = vjp((cols[0] * prods[-1]).astype(self.cd).astype(f32))
        return ([hid] + [v.astype(self.cd) for v in d_first]
                + [prods[-1] * hid.astype(f32)])


def _summed(prods, cols):
    """The rows' gradient: the sum of the first matrices' shares."""
    return [sum(prods)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_kernel(activate, block, weights, x, gate, tok, counts):
    """The kernel form: the landed rows gathered, a call of the first
    products with ``activate`` as its epilogue, and a call of the output
    product that weighs each row by its pick and adds it to its token's
    row."""
    cd = x.dtype
    xs = _landed_rows(x, tok, jnp.sum(counts), block)
    act, = grouped_matmul.grouped_products(
        counts, [xs],
        [(0, weights[k].astype(cd), False) for k in ("w1", "wg")
         if k in weights],
        _Activated(activate), [cd], name="moe_gmm_hidden")
    return grouped_matmul.grouped_products(
        counts, [act], [(0, weights["w2"].astype(cd), False)], _weighted, [],
        cols=[gate.astype(jnp.float32)[:, None]],
        add_to=(tok, x.shape[0]), name="moe_gmm_out")[0]


def _grouped_kernel_fwd(activate, block, weights, x, gate, tok, counts):
    return (_grouped_kernel(activate, block, weights, x, gate, tok, counts),
            (weights, x, gate, tok, counts))


@under_scope("moe_experts")
def _kernel_backward(activate, block, res, dout):
    """One fused call recomputes the first products and the activation,
    takes ``dy @ w2^T`` (which serves d(gate) = <dy @ w2^T, hid> and
    d(hid) = gate * it, as the XLA form has it) and the activation's
    derivative (``_Backward``); two calls of transposed products give the
    weight gradients, a call of two products the rows' gradient, added to
    the tokens' rows inside it."""
    weights, x, gate, tok, counts = res
    cd = x.dtype
    first = [k for k in ("w1", "wg") if k in weights]
    landed = jnp.sum(counts)
    xs = _landed_rows(x, tok, landed, block)
    # the cotangent has passed the layer's rounding to the compute dtype
    dys = _landed_rows(dout.astype(cd), tok, landed, block)
    g = gate.astype(jnp.float32)[:, None]
    hid, *d_first, dgate = grouped_matmul.grouped_products(
        counts, [xs, dys],
        [(0, weights[k].astype(cd), False) for k in first]
        + [(1, weights["w2"].astype(cd), True)],
        _Backward(activate, jnp.dtype(cd)), [cd] * (1 + len(first)),
        cols=[g], row_sum=True, name="moe_gmm_bwd")
    dw = dict(zip(first, grouped_matmul.grouped_transposed(
        counts, xs, d_first, weights["w1"].dtype, name="moe_gmm_t_hidden")))
    dw["w2"], = grouped_matmul.grouped_transposed(
        counts, hid, [dys], weights["w2"].dtype, scale=g, name="moe_gmm_t_out")
    dx, = grouped_matmul.grouped_products(
        counts, d_first,
        [(i, weights[k].astype(cd), True) for i, k in enumerate(first)],
        _summed, [], add_to=(tok, x.shape[0]), name="moe_gmm_dx")
    dgate = jnp.where(jnp.arange(gate.shape[0]) < landed, dgate, 0.0)
    return (dw, dx.astype(x.dtype), dgate.astype(gate.dtype), None, None)


_grouped_kernel.defvjp(_grouped_kernel_fwd, _kernel_backward)


def expert_param_specs(moe: MoE, axis: str = EXPERT_AXIS):
    """PartitionSpecs sharding the stacked expert leaves over ``expert``;
    the router and a shared expert are replicated."""
    stacked = {"w1": P(axis, None, None), "wg": P(axis, None, None),
               "b1": P(axis, None), "w2": P(axis, None, None),
               "b2": P(axis, None)}
    return {name: stacked.get(name, P()) for name in moe._parameters}
