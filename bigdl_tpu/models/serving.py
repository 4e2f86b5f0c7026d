"""Continuous batching LM serving engine (round 5, VERDICT #6).

The bucketed ``models/lm_server.py`` groups requests by exact prompt
length and decodes whole batches in lockstep: one long generation blocks
its bucket, and mixed-length traffic fragments into tiny batches. This
engine replaces lockstep with SLOTS (the vLLM-style iteration-level
scheduler, built TPU-first on static shapes):

- the model sits permanently in *continuous* decode mode: (slots, L) KV
  caches with a PER-ROW ``decode_pos`` (``nn.attention
  ._attend_decode_continuous``) — every slot lives at its own position in
  its own sequence, and ONE jitted step program advances them all;
- a new request prefills OUT-OF-BAND as a b=1 forward in FIXED-SIZE
  CHUNKS (``prefill_mode="chunked"``, the default): ⌈(L-1)/C⌉ chunks of
  ``prefill_chunk`` tokens through the warm-cache chunked attention
  branch plus one single-token step for the last prompt token — exactly
  TWO compiled programs regardless of prompt length, where the old
  per-length prefill compiled one program per distinct length (the
  compile storm ROADMAP #1 tracked; graftlint JG013's frozen fire
  fixture is that pre-fix code). ``prefill_mode="bucketed"`` is the
  fallback for attention paths that can't take the masked chunk: the
  prompt pads to its power-of-two ``pow2_bucket`` length and one
  wrapper specializes per bucket (O(log max_len) programs). Either way
  a jitted insert then scatters the (1, L) cache into a free slot row
  and sets that row's ``decode_pos`` — admission never recompiles or
  disturbs running slots;
- steps dispatch in blocks of ``decode_block`` tokens (a ``lax.scan`` —
  amortizes the per-dispatch host cost); finished rows (eos/budget) free
  their slot at the next block boundary and the queue admits strictly
  FIFO, so no request can be starved (the ADVICE round-4 finding against
  the bucketed ``_gather``).

Dead slots keep computing garbage (their rows are never read) — the TPU
trade: wasted lanes are cheaper than a recompile or a dynamic shape.

Round 9 layers two first-class serving modes onto this engine:

- CROSS-REQUEST KV PREFIX CACHE (``models/prefix_cache.py``, on by
  default in chunked mode; ``BIGDL_PREFIX_CACHE=0`` disables): the
  chunked prefill snapshots its per-request state partition at every
  FULL chunk boundary into a per-model trie keyed by a rolling hash of
  the chunk-aligned token prefix. An admission sharing a cached prefix
  copies the b=1 partition and chunk-prefills only the uncached tail —
  TTFT collapses on hits (``bigdl_serving_ttft_hit_seconds`` vs
  ``_miss_``) while greedy outputs stay bit-identical to a cold prefill
  (a chunk-boundary resume reproduces the cold run's exact chunk
  partition, hence its exact floating-point reductions). Size-bounded
  with counted LRU eviction.
- SPECULATIVE DECODE (``draft=...``, ``BIGDL_SPEC_LEN``): the draft
  model lives in its own (slots, L) continuous decode state, prefilled
  and slot-inserted alongside the target on every admission. Each round
  the draft proposes ``spec_len`` tokens per row (a ``lax.scan`` of
  single-token steps) and the target verifies carried-token + proposals
  in ONE multi-token continuous forward — the chunked verification path
  (``nn.attention._attend_decode_continuous``'s chunk branch: per-row
  write positions, per-row masks). Per-row first-mismatch acceptance
  emits 1..spec_len+1 tokens per dispatch and rolls BOTH caches back to
  each row's accepted boundary (a per-row ``decode_pos`` shift; the
  stale writes sit behind the position mask until overwritten).
  Greedy-only — acceptance is exact argmax match, which is what keeps
  outputs bit-identical to the non-speculative path. ``decode_block``
  is ignored in this mode: one round is one dispatch.

Restrictions: rope models only (additive positional-encoding modules
track a shared scalar position), no beam search. Sampling is the server's
(greedy/temperature/top_k/top_p via ``generation.sample_token``).

``ContinuousLMServer`` exposes the same ``submit()/close()`` surface as
``LMServer``, so ``make_http_server`` and ``apps.transformer serve
--continuous`` reuse it unchanged.
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import functional_apply
from bigdl_tpu.models.generation import (_decode_modules,
                                         _shift_decode_pos,
                                         build_bucketed_prefill_fn,
                                         build_chunked_prefill_fns,
                                         deserialize_prefill_state,
                                         partition_prefill_state,
                                         sample_token,
                                         serialize_prefill_state)
from bigdl_tpu.models.lm_server import drain_queue, fail_requests
from bigdl_tpu.models.prefix_cache import (DEFAULT_PREFIX_CACHE_MB,
                                           prefix_cache_for)
from bigdl_tpu.telemetry import get_registry, instruments, span, tracing
from bigdl_tpu.telemetry.profiling import (sample_device_memory,
                                           tracked_jit)
from bigdl_tpu.utils.util import pow2_bucket

# Smallest prefill length bucket (prefill_mode="bucketed"): prompts
# shorter than this share one program instead of minting one per small
# power of two. The top bucket saturates at max_len.
_PREFILL_BUCKET_LO = 16

# One id per submitted request, process-wide: the Chrome-trace async
# lifecycle key (serving.request) and the rid arg on every phase span.
# itertools.count is GIL-atomic — submit() runs on client threads.
_REQUEST_IDS = itertools.count(1)


@dataclass
class HandoffCursor:
    """The migratable request cursor: everything a PEER replica needs to
    finish an interrupted request with bit-identical greedy output —
    re-prefilling ``ids + emitted`` reproduces the donor's exact chunked
    reductions, so the continuation is the continuation the unkilled run
    would have produced. Sampled (non-greedy) resumes are best-effort:
    the admission key advances per admission, so a migrated draw comes
    from a fresh stream."""
    ids: List[int]                      # the original prompt
    emitted: List[int]                  # tokens produced before the cut
    max_new: int                        # the ORIGINAL token budget


class ReplicaUnavailable(RuntimeError):
    """``submit()`` failed because this replica cannot serve. ``cursor``
    (when set) carries the accepted request's resume state — the caller
    (the router) re-dispatches it to a peer; ``cursor=None`` means the
    request never entered this replica and can simply be retried."""

    def __init__(self, message: str, cursor: Optional[HandoffCursor] = None):
        super().__init__(message)
        self.cursor = cursor


class ServerDraining(ReplicaUnavailable):
    """Planned unavailability (SIGTERM/drain): the replica is finishing
    or handing off its in-flight work — retry elsewhere, this process is
    shutting down cleanly."""


class ServerDead(ReplicaUnavailable):
    """Unplanned unavailability (decode/worker failure): the donated
    cache state is gone and the server will never serve again — retry
    elsewhere against a healthy replica; this one needs a restart."""


@dataclass
class RequestTiming:
    """Raw times of one request, ``time.perf_counter()`` seconds, always
    recorded (tracer on or off). ``token_times`` has one entry for each
    token THIS server emitted, in order (a resumed ``emitted`` prefix has
    none): the moment the token reached the host, which for the first is
    ``first_token`` (the admission sample's fetch, before the insert) and
    for the others the fetch of their decode block, so the tokens of one
    block share a time. ``submitted <= admitted <= first_token <=
    token_times[...] <= done``; ``admitted`` and ``first_token`` are None
    for a request that failed or was answered before admission."""
    submitted: float
    admitted: Optional[float] = None        # admission started
    first_token: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    done: Optional[float] = None


@dataclass
class _Request:
    ids: List[int]
    max_new: int
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[List[int]] = None
    error: Optional[str] = None
    t_submit: float = 0.0               # perf_counter at submit (TTFT/SLO)
    timing: Optional[RequestTiming] = None
    rid: int = 0                        # trace-lifecycle id (serving.request)
    emitted0: List[int] = field(default_factory=list)  # resume-cursor prefix
    state_blob: Optional[bytes] = None  # shipped prefill partition (disagg)
    handoff: Optional[HandoffCursor] = None
    fail_kind: Optional[str] = None     # "draining" | "dead" | None


class _Slot:
    __slots__ = ("req", "emitted", "new_count")

    def __init__(self, req):
        self.req = req
        self.emitted: List[int] = []
        self.new_count = 0


def _build_insert_fn(registry):
    """Jitted scatter of a prefilled b=1 cache into slot row ``slot``
    (slot/plen are traced scalars, so ONE compile per buffer-tree
    signature). Model-agnostic tree surgery — the same wrapper serves
    the target insert and, in speculative mode, the draft insert as a
    second signature."""
    def insert_prog(big, small, slot, plen):
        flat_b, treedef = jax.tree_util.tree_flatten_with_path(big)
        flat_s = jax.tree_util.tree_flatten_with_path(small)[0]
        out = []
        for (kp, bg), (_, sm) in zip(flat_b, flat_s):
            name = str(kp[-1])
            if "k_cache" in name or "v_cache" in name:
                # the chunked-prefill template cache is padded to a
                # whole number of chunks; only the first max_len entries
                # are live (anything past the prompt is masked pad
                # garbage) — slice before the scatter (no-op when the
                # template is not longer than the slot row; a spec-mode
                # slot row carries spec_len+1 slack the template lacks,
                # and the tail past the copy stays masked the same way)
                out.append(jax.lax.dynamic_update_slice(
                    bg, sm.astype(bg.dtype)[:, :bg.shape[1]],
                    (slot,) + (0,) * (bg.ndim - 1)))
            elif "decode_pos" in name:
                out.append(jax.lax.dynamic_update_slice(
                    bg, plen[None].astype(bg.dtype), (slot,)))
            else:
                out.append(bg)
        return jax.tree_util.tree_unflatten(treedef, out)

    return tracked_jit(insert_prog, site="serving.insert",
                       registry=registry,
                       donate_argnums=(0,))


class _PrefillPipeline:
    """The out-of-band b=1 admission-prefill machine for ONE model.

    PR 15 built this inline for the target; speculative serving runs
    the SAME admission prefill against the draft (its (slots, L)
    continuous cache needs the prompt too), so the machinery — the
    decode-mode templates, the O(1) program set, the trace-time flag
    context, and now the prefix trie — lives here once and the server
    instantiates it per model."""

    def __init__(self, model, *, mode: str, chunk: int, slots: int,
                 max_len: int, big_len: int, registry, site: str,
                 prefix_bytes: int = 0):
        mhas, pes, heads = _decode_modules(model)
        if pes:
            raise ValueError(
                "continuous batching requires a rope model (additive "
                "positional encodings track one shared position; "
                "build_lm(rope=True))")
        if not mhas:
            raise ValueError("model has no attention layers to cache")
        self.model = model
        self.mhas, self.heads = mhas, heads
        self.mode, self.chunk, self.max_len = mode, chunk, max_len
        # run() flips module-level trace flags and threads the template
        # state — serialize it: the worker's admission prefill and a
        # router thread's prefill_handoff() may hit the same pipeline
        self._run_lock = threading.Lock()
        model.evaluate_mode()
        # single-request decode template (the prefill signature) FIRST,
        # then the persistent continuous state. The chunked template
        # cache is padded up to a whole number of chunks so the final
        # (right-padded) chunk's k/v write never clips against the cache
        # end — the insert slices the copy back down to the slot row.
        if mode == "chunked":
            self.cache_len = -(-max_len // chunk) * chunk
        else:
            self.cache_len = max_len
        for m in mhas:
            m.enable_decode(1, self.cache_len)
        for m in heads:
            m.enable_decode()
        _, small0 = model.functional_state()
        # COPY the template leaves: non-cache buffers (e.g. a quantized
        # model's int8 weights live in the buffer tree) are otherwise the
        # very arrays the donating step/insert programs consume — the
        # first admission would delete the prefill template's references
        self.small_bufs0 = jax.tree_util.tree_map(jnp.copy, small0)
        for m in mhas:
            m.enable_decode(slots, big_len, continuous=True)
        self.params, self.buffers = model.functional_state()
        # the O(1) prefill program set, built BEFORE the worker thread
        # starts (wrappers are cheap; XLA programs compile lazily inside
        # tracked_jit at first dispatch, counted per signature in
        # bigdl_compiles_total{site})
        if mode == "chunked":
            (self.chunk_fn, self.last_fn, self.state0,
             self.statics, self.merge) = build_chunked_prefill_fns(
                model, self.small_bufs0, site=site, registry=registry)
            self.bucket_fn = None
            # the cross-request prefix trie rides on the MODEL (warm
            # prefixes survive a server restart over the same weights;
            # __getstate__ pops it). Chunked mode only — bucketed
            # prefill has no chunk-aligned snapshots to key on.
            self.prefix = (prefix_cache_for(
                model, chunk=chunk, cache_len=self.cache_len,
                max_bytes=prefix_bytes) if prefix_bytes > 0 else None)
        else:
            self.chunk_fn = self.last_fn = None
            self.bucket_fn = build_bucketed_prefill_fn(
                model, site=site, registry=registry)
            self.prefix = None

    @property
    def fns(self):
        """The O(1) prefill program set — chunked mode holds the chunk +
        last-token pair, bucketed mode one wrapper that specializes per
        power-of-two bucket. Collapsed from the pre-PR-15 per-prompt-
        length LRU (one program per distinct length, the compile storm
        graftlint JG013's fire fixture preserves)."""
        fns = {"chunk": self.chunk_fn, "last": self.last_fn,
               "bucket": self.bucket_fn}
        return {k: v for k, v in fns.items() if v is not None}

    def single_mode(self, prefilled: bool, all_logits: bool = False):
        """Context: flip the attention modules to single-request decode
        semantics for tracing/running the b=1 prefill programs.

        ``prefilled`` is the trace-time cache temperature: True traces
        the warm-cache masked branch (chunked prefill — correct on a
        cold cache too, the position mask excludes unwritten slots),
        False the cold causal fast path (bucketed prefill, which always
        starts from scratch). ``all_logits`` flips the LM heads to emit
        every position (the bucketed program reads the true last token
        at a traced index inside the padded bucket)."""
        pipe = self

        class _Ctx:
            def __enter__(self):
                for m in pipe.mhas:
                    m._continuous = False
                    m._decode_prefilled = prefilled
                if all_logits:
                    for h in pipe.heads:
                        h._decode_all = True
                return self

            def __exit__(self, *a):
                for m in pipe.mhas:
                    m._continuous = True
                    m._decode_prefilled = True
                if all_logits:
                    for h in pipe.heads:
                        h._decode_all = False

        return _Ctx()

    def _prefill_chunked(self, ids: List[int]):
        """Chunked b=1 prompt prefill: ⌈(L-1)/C⌉ fixed-width chunks that
        write k/v at the true cache positions (final chunk right-padded,
        pads masked and re-covered via the in-program ``decode_pos``
        rewind), then ONE single-token step for the last prompt token
        whose (1, V) log-probs feed the admission sample. Two compiled
        programs total, any L — and with the prefix trie, only the
        UNCACHED tail's chunks are dispatched on a hit."""
        c = self.chunk
        n = len(ids) - 1        # last token runs as the lp-producing step
        hit = 0
        state = None
        if self.prefix is not None:
            # deepest cached chunk-aligned prefix of the chunked portion
            # (already an owned copy, safe to donate into the chunk loop)
            hit, state = self.prefix.match(ids[:n])
        if state is None:
            # both prefill programs donate the per-request STATE
            # partition (caches + positions — in-place updates across
            # the chunk loop); hand them an OWNED copy so the template
            # survives this admission. Shared buffers (a quantized
            # model's int8 weights) ride along non-donated: the
            # per-admission copy scales with the b=1 cache, never with
            # model size.
            state = [jnp.copy(x) for x in self.state0]
        statics = self.statics
        for start in range(hit, n, c):
            valid = min(c, n - start)
            chunk = np.ones((1, c), np.float32)   # pad id 1: any valid id
            chunk[0, :valid] = ids[start:start + valid]
            state = self.chunk_fn(self.params, state, statics,
                                  jnp.asarray(chunk),
                                  jnp.int32(start + valid))
            if self.prefix is not None and valid == c:
                # FULL-chunk boundary: the live state IS the snapshot —
                # the trie copies it (known prefixes skip even the copy)
                # before the next dispatch donates it away. Ragged final
                # chunks are never cached: a mid-chunk resume would
                # regroup the tail's reductions and break bit-exactness.
                self.prefix.put(ids[:start + valid], state)
        last = np.asarray([[ids[-1]]], np.float32)
        lp, state = self.last_fn(self.params, state, statics,
                                 jnp.asarray(last))
        # the insert consumes the FULL small tree (structure must match
        # the big tree leaf-for-leaf); merge is host-side, copy-free
        return lp, self.merge(state, statics), hit

    def _prefill_bucketed(self, ids: List[int]):
        """Length-bucketed b=1 prompt prefill (fallback mode): the
        prompt right-pads to its power-of-two bucket and runs the
        standard cold causal prefill — one program per BUCKET
        (O(log max_len) total), with the true last token's log-probs
        read at a traced index."""
        plen = len(ids)
        cap = self.cache_len
        bsz = pow2_bucket(plen, min(_PREFILL_BUCKET_LO, cap), cap)
        prompt = np.ones((1, bsz), np.float32)
        prompt[0, :plen] = ids
        lp, bufs = self.bucket_fn(self.params, self.small_bufs0,
                                  jnp.asarray(prompt), jnp.int32(plen - 1))
        return lp, bufs, 0

    def run(self, ids: List[int]):
        """Mode dispatch + compile accounting: returns ``(lp, small
        buffer tree, prefix-hit depth, programs built)`` — any program
        the flight recorder built during this prefill counts as serving
        recompile churn (per NEW SIGNATURE — a bucketed wrapper minting
        its second bucket counts exactly like a fresh program build)."""
        with self._run_lock:
            fns = self.fns
            before = sum(fn.compiles for fn in fns.values())
            if self.mode == "bucketed":
                with self.single_mode(prefilled=False, all_logits=True):
                    lp, small, hit = self._prefill_bucketed(ids)
            else:
                with self.single_mode(prefilled=True):
                    lp, small, hit = self._prefill_chunked(ids)
            built = sum(fn.compiles for fn in fns.values()) - before
            return lp, small, hit, built

    def disable(self):
        for m in self.mhas + self.heads:
            m.disable_decode()


class ContinuousLMServer:
    """Slot-scheduled continuous-batching server over one rope LM."""

    def __init__(self, model, *, slots: int = 8, max_len: int = 256,
                 decode_block: int = 8, max_new_tokens: int = 64,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 0.0, greedy: bool = False,
                 eos_id: Optional[int] = None, seed: int = 0,
                 registry=None, prefill_mode: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 draft=None, spec_len: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_mb: Optional[float] = None,
                 chaos=None):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        # prompt prefill strategy (both O(1)-compile; ROADMAP #1):
        # "chunked" (default) = fixed-size chunks through the warm-cache
        # chunked attention branch, two programs total; "bucketed" =
        # pad the prompt to its power-of-two bucket, one program per
        # bucket — the fallback for attention paths that can't take the
        # masked multi-token chunk. Env levers mirror the args so a
        # deployment can flip modes without code changes.
        mode = (prefill_mode if prefill_mode is not None
                else os.environ.get("BIGDL_PREFILL_MODE", "chunked"))
        if mode not in ("chunked", "bucketed"):
            raise ValueError(f"prefill_mode must be 'chunked' or "
                             f"'bucketed', got {mode!r}")
        chunk = int(prefill_chunk if prefill_chunk is not None
                    else os.environ.get("BIGDL_PREFILL_CHUNK", "128"))
        if chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        # a chunk wider than the cache buys nothing and multiplies the
        # template-cache memory and per-prompt prefill work (prompts
        # never exceed max_len - max_new); clamp rather than reject so
        # the 128 default composes with small test/serving caches
        chunk = min(chunk, max_len)
        self.prefill_mode = mode
        self.prefill_chunk = chunk
        # speculative decode config (mirroring the prefill levers:
        # constructor args first, BIGDL_SPEC_* env as deployment default)
        self.draft = draft
        if draft is not None:
            if draft is model:
                raise ValueError(
                    "draft must be a separate module instance (one module "
                    "cannot hold two decode states at once)")
            if not greedy:
                raise ValueError(
                    "speculative serving is greedy-only: acceptance is "
                    "exact argmax match against the target, which is what "
                    "keeps outputs bit-identical to non-speculative decode")
            k = int(spec_len if spec_len is not None
                    else os.environ.get("BIGDL_SPEC_LEN", "4"))
            if k < 1:
                raise ValueError("spec_len must be >= 1")
            self.spec_len = k
        else:
            self.spec_len = 0
        # prefix-cache config: on by default in chunked mode (the cache
        # keys on chunk-aligned snapshots; bucketed prefill has none)
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "BIGDL_PREFIX_CACHE", "1").lower() not in (
                    "0", "off", "false", "no")
        mb = float(prefix_cache_mb if prefix_cache_mb is not None
                   else os.environ.get("BIGDL_PREFIX_CACHE_MB",
                                       str(DEFAULT_PREFIX_CACHE_MB)))
        prefix_bytes = (int(mb * (1 << 20))
                        if (prefix_cache and mode == "chunked") else 0)
        self.prefix_cache_enabled = prefix_bytes > 0
        # telemetry (docs/OBSERVABILITY.md): TTFT / per-token latency /
        # queue depth / slot occupancy — the serving SLO surface, exposed
        # by make_http_server as GET /metrics
        self.registry = registry if registry is not None else get_registry()
        self._tm = instruments(self.registry)
        self._tm.serving_slots_total.set(slots)
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.decode_block = max(1, int(decode_block))
        self.max_new_tokens = max_new_tokens
        self.sampling = dict(temperature=temperature, top_k=top_k,
                             top_p=top_p, greedy=greedy)
        self.eos_id = eos_id
        self._seed = seed
        # Disjoint key streams, collision-free by construction: the old
        # ad-hoc arithmetic (seed + n_admitted*7919 + 1 for admissions,
        # seed + steps*31 + 17 for decode blocks) lands both families on
        # the SAME PRNGKey for some (n, steps) pair — e.g. admission 10
        # and step 2554 — correlating an admitted token draw with a whole
        # decode block (found by graftlint JG003).
        self._admit_key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
        self._step_key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
        self._steps = 0
        self._n_served = 0
        self._n_admitted = 0

        # continuous caches carry spec_len+1 rows of length slack in
        # speculative mode: a request finishing at max_len still runs a
        # final verification chunk whose writes land up to spec_len
        # positions past its last committed token (masked, then rolled
        # back — but the cache must physically hold them)
        big_len = max_len + (self.spec_len + 1 if draft is not None else 0)
        self._pipeline = _PrefillPipeline(
            model, mode=mode, chunk=chunk, slots=slots, max_len=max_len,
            big_len=big_len, registry=self.registry,
            site="serving.prefill", prefix_bytes=prefix_bytes)
        self._mhas, self._heads = self._pipeline.mhas, self._pipeline.heads
        self.params = self._pipeline.params
        self.buffers = self._pipeline.buffers
        if draft is not None:
            self._d_pipeline = _PrefillPipeline(
                draft, mode=mode, chunk=chunk, slots=slots,
                max_len=max_len, big_len=big_len, registry=self.registry,
                site="serving.draft_prefill", prefix_bytes=prefix_bytes)
            self.d_params = self._d_pipeline.params
            self.d_buffers = self._d_pipeline.buffers
        else:
            self._d_pipeline = None
            self.d_params = self.d_buffers = None
        self._step_fn = None
        self._insert_fn = None
        self._spec_fn = None
        self._prefix_evictions_seen = 0

        # serving-plane chaos injectors (resilience/chaos.py): anything
        # with an on_decode_block(server) hook is polled at each block
        # boundary INSIDE the decode try — a raising injector (the
        # kill-replica drill) exercises the real die path mid-stream
        self._chaos = [inj for inj in (chaos or [])
                       if hasattr(inj, "on_decode_block")]
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._stop = threading.Event()
        self._dead: Optional[str] = None     # set once; never cleared
        self._draining: Optional[str] = None  # set once; distinct from dead
        # drain()/close() lifecycle arbitration: first caller wins the
        # state transition, every later call is a harmless no-op sweep —
        # close() stays idempotent under a concurrent drain
        self._lifecycle_lock = threading.Lock()
        # _prefix_evictions_seen read-modify-write happens on the worker
        # (admission) AND router threads (prefill_handoff) — serialize it
        self._prefix_sync_lock = threading.Lock()
        # slot bookkeeping is touched by the worker thread AND by
        # close()/client threads — every mutation of _free/_active holds
        # this lock (found by graftlint JG015: close() clearing _active
        # concurrently with the worker's admit/finish could double-free
        # a slot when the join below times out)
        self._state_lock = threading.Lock()
        self._free = list(range(slots))
        self._active: dict = {}          # slot -> _Slot
        self._last_tok = np.ones((slots,), np.int32)
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="lm-server-continuous")
        self._worker.start()

    # ------------------------------------------------------------ client API
    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               timeout: Optional[float] = None, *,
               emitted: Optional[List[int]] = None,
               state: Optional[bytes] = None) -> List[int]:
        """``submit_timed(...)[0]``: the tokens alone."""
        return self.submit_timed(prompt_ids, max_new_tokens, timeout,
                                 emitted=emitted, state=state)[0]

    def submit_timed(self, prompt_ids,
                     max_new_tokens: Optional[int] = None,
                     timeout: Optional[float] = None, *,
                     emitted: Optional[List[int]] = None,
                     state: Optional[bytes] = None
                     ) -> Tuple[List[int], RequestTiming]:
        """Serve one prompt; returns ``(tokens, RequestTiming)``: the raw
        per-request times first-token latency and inter-token gaps are
        computed from. ``emitted`` resumes a migrated request from
        its ``HandoffCursor``: the server re-prefills ``prompt + emitted``
        (deterministic, so the greedy continuation is bit-identical to
        the donor's unkilled run) and the result INCLUDES the resumed
        prefix. ``state`` admits a shipped prefill partition
        (``serialize_prefill_state`` from a prefill replica) instead of
        prefilling locally — the disaggregated decode path."""
        ids = [int(t) for t in prompt_ids]
        if not ids:
            raise ValueError("empty prompt")
        max_new = int(self.max_new_tokens if max_new_tokens is None
                      else max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(ids) + max_new > self.max_len:
            raise ValueError(f"prompt {len(ids)} + max_new {max_new} "
                             f"exceeds the server max_len {self.max_len}")
        emitted0 = [int(t) for t in (emitted or [])]
        if emitted0:
            # a cursor that already satisfied its budget (or hit eos)
            # needs no decode at all — the donor just never got to
            # deliver the result
            now = time.perf_counter()
            if self.eos_id is not None and self.eos_id in emitted0:
                return (emitted0[:emitted0.index(self.eos_id) + 1][:max_new],
                        RequestTiming(now, done=now))
            if len(emitted0) >= max_new:
                return emitted0[:max_new], RequestTiming(now, done=now)
        if state is not None and self.draft is not None:
            raise ValueError(
                "state handoff is incompatible with speculative serving "
                "(the draft replica's partition does not travel)")
        if self._dead is not None:
            # fail IMMEDIATELY: a dead worker loop will never drain the
            # queue, and waiting out the client timeout helps nobody
            raise ServerDead(f"server is dead: {self._dead}")
        if self._draining is not None:
            # distinct from dead: the replica is going away ON PURPOSE —
            # the caller should retry elsewhere, nothing is lost
            raise ServerDraining(f"server is draining: {self._draining}")
        req = _Request(ids, max_new)
        req.emitted0 = emitted0
        req.state_blob = state
        req.rid = next(_REQUEST_IDS)
        req.t_submit = time.perf_counter()
        req.timing = RequestTiming(req.t_submit)
        # request lifecycle: one async lane per rid in the Chrome trace —
        # submit opens it, admission marks it, completion/failure closes
        # it; the queue_wait/prefill/insert spans carry the same rid
        tracing.async_begin("serving.request", req.rid,
                            prompt_len=len(ids), max_new=max_new)
        self._queue.put(req)
        if not req.done.is_set() and (self._dead is not None
                                      or self._draining is not None):
            # the worker stopped between the check and the enqueue; its
            # final sweep may have missed this request — fail it here
            # (with a cursor, so a router can still re-dispatch it)
            if self._dead is not None:
                self._fail_handoff(req, emitted0,
                                   f"server is dead: {self._dead}", "dead")
            else:
                self._fail_handoff(req, emitted0,
                                   f"server is draining: {self._draining}",
                                   "draining")
        self._tm.serving_queue_depth.set(self._queue.qsize())
        if not req.done.wait(timeout):
            raise TimeoutError("decode did not complete in time")
        if req.error is not None:
            if req.fail_kind == "draining":
                raise ServerDraining(req.error, cursor=req.handoff)
            if req.fail_kind == "dead":
                raise ServerDead(req.error, cursor=req.handoff)
            raise RuntimeError(req.error)
        return req.result, req.timing

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (the /health SLO signal)."""
        return self._queue.qsize()

    @property
    def dead_reason(self) -> Optional[str]:
        """Why the worker loop stopped serving (None while healthy). Once
        set, every ``submit()`` raises immediately — restart the server;
        the donated-buffer state after a decode failure is not
        recoverable in place."""
        return self._dead

    @property
    def drain_reason(self) -> Optional[str]:
        """Why the server stopped ADMITTING (None unless draining).
        Distinct from ``dead_reason``: a draining replica failed nothing
        — every interrupted request left with a ``HandoffCursor`` and
        ``/health`` reports ``draining`` so a router stops routing here
        without declaring the replica lost."""
        return self._draining

    def drain(self, reason: str = "drain requested") -> None:
        """Graceful shutdown (the SIGTERM path): stop admitting, stop
        the decode loop at the next block boundary, and hand every
        accepted-but-unfinished request off as a ``HandoffCursor``
        (prompt ids + emitted tokens + budget) raised to its waiting
        ``submit()`` as ``ServerDraining`` — a router re-dispatches the
        cursor to a peer, whose deterministic re-prefill keeps greedy
        outputs bit-identical to an unkilled run. Idempotent, and safe
        to race with ``close()``: the first lifecycle call wins, later
        ones only re-sweep (finding nothing)."""
        with self._lifecycle_lock:
            if self._dead is not None or self._draining is not None:
                return
            self._draining = reason
        self._tm.serving_drains_total.inc()
        self._stop.set()
        self._worker.join(timeout=10)
        self._sweep_stranded()

    def close(self):
        """Stop the worker and fail anything still pending. Idempotent,
        including under a CONCURRENT ``drain()``: both sides snapshot-
        and-clear the slot table under ``_state_lock``, so each stranded
        request is failed exactly once — and when the drain got there
        first, with its handoff cursor intact (``_fail_handoff`` never
        overwrites a request that already completed or failed)."""
        self._stop.set()
        self._worker.join(timeout=10)
        for p in self._pipelines:
            p.disable()
        self._sweep_stranded()

    def prefill_handoff(self, prompt_ids,
                        emitted: Optional[List[int]] = None) -> bytes:
        """Run the admission prefill WITHOUT taking a slot and return
        the serialized handoff partition (last-token log-probs + b=1
        state) for a DECODE replica's ``submit(..., state=blob)`` — the
        prefill half of prefill/decode disaggregation. Raises
        ``ServerDraining``/``ServerDead`` like ``submit`` so the router's
        health logic applies unchanged."""
        ids = ([int(t) for t in prompt_ids]
               + [int(t) for t in (emitted or [])])
        if not ids:
            raise ValueError("empty prompt")
        if self._dead is not None:
            raise ServerDead(f"server is dead: {self._dead}")
        if self._draining is not None:
            raise ServerDraining(f"server is draining: {self._draining}")
        if self._d_pipeline is not None:
            raise ValueError("prefill handoff is incompatible with "
                             "speculative serving (the draft partition "
                             "does not travel)")
        with span("serving.prefill", plen=len(ids), rid=0,
                  mode=self.prefill_mode):
            lp, small, hit, built = self._pipeline.run(ids)
        if built:
            self._tm.serving_recompiles_total.inc(built)
        self._sync_prefix_metrics(hit)
        state = partition_prefill_state(small)[0]
        return serialize_prefill_state(lp, state)

    @property
    def batches_served(self) -> int:
        return self._n_served

    @property
    def requests_admitted(self) -> int:
        """Requests admitted into slots over this server's lifetime —
        the trigger the serving-plane chaos injectors key off."""
        return self._n_admitted

    @property
    def decode_blocks(self) -> int:
        """Decode blocks started (1-based inside the current block) —
        the other chaos trigger."""
        return self._steps

    # ------------------------------------------------------------- programs
    @property
    def _pipelines(self):
        """The live prefill pipelines (target always; draft in
        speculative mode)."""
        return ([self._pipeline] if self._d_pipeline is None
                else [self._pipeline, self._d_pipeline])

    @property
    def _prefill_fns(self):
        """The target's O(1) prefill program set (see
        ``_PrefillPipeline.fns``)."""
        return self._pipeline.fns

    @property
    def _prefill_cache_len(self):
        """Template cache length of the target prefill pipeline."""
        return self._pipeline.cache_len

    def _run_prefill(self, ids: List[int]):
        """Admission prefill across every pipeline: the target produces
        the sampling log-probs; in speculative mode the DRAFT prefills
        the same prompt right after (its continuous cache needs the
        prompt too — each pipeline keeps its own prefix trie over its
        own state shapes, so a hot prefix skips chunks for both).
        Compile accounting: any program the flight recorder built during
        this prefill counts as serving recompile churn (per NEW
        SIGNATURE — a bucketed wrapper minting its second bucket counts
        exactly like a fresh program build)."""
        lp, small, hit, built = self._pipeline.run(ids)
        d_small = None
        if self._d_pipeline is not None:
            _d_lp, d_small, _d_hit, d_built = self._d_pipeline.run(ids)
            built += d_built
        if built:
            self._tm.serving_recompiles_total.inc(built)
        self._sync_prefix_metrics(hit)
        return lp, small, d_small, hit

    def _sync_prefix_metrics(self, hit: int) -> None:
        """Mirror the trie's plain counters into the registry families.
        Hit/miss count ADMISSIONS (the target trie's verdict — one count
        per prefill, so hit rate reads directly as hits/(hits+misses));
        evictions and held bytes aggregate over both pipelines' tries in
        speculative mode."""
        caches = [p.prefix for p in self._pipelines
                  if p.prefix is not None]
        if not caches:
            return
        (self._tm.prefix_cache_hits if hit
         else self._tm.prefix_cache_misses).inc()
        with self._prefix_sync_lock:
            ev = sum(pc.evictions for pc in caches)
            if ev > self._prefix_evictions_seen:
                self._tm.prefix_cache_evictions.inc(
                    ev - self._prefix_evictions_seen)
                self._prefix_evictions_seen = ev
        self._tm.prefix_cache_bytes.set(sum(pc.nbytes for pc in caches))

    def _insert(self):
        """The slot-insert program (built on first use; the draft insert
        in speculative mode is the SAME wrapper specializing on the
        draft's buffer-tree signature)."""
        if self._insert_fn is None:
            self._insert_fn = _build_insert_fn(self.registry)
            self._tm.serving_recompiles_total.inc()
        return self._insert_fn

    def _step(self):
        """Jitted decode_block-token step over ALL slots."""
        if self._step_fn is None:
            model = self.model
            sampling = self.sampling
            block = self.decode_block

            def run(params, bufs, toks, key):
                def one(carry, kk):
                    bufs, tok = carry
                    lp, bufs = functional_apply(
                        model, params, bufs,
                        tok[:, None].astype(jnp.float32), training=False)
                    nxt = sample_token(lp[:, -1], kk, **sampling)
                    return (bufs, nxt), nxt

                keys = jax.random.split(key, block)
                (bufs, _), out = jax.lax.scan(one, (bufs, toks), keys)
                return out.T, bufs      # (slots, block)

            self._step_fn = tracked_jit(run, site="serving.step",
                                        registry=self.registry,
                                        donate_argnums=(1,))
            self._tm.serving_recompiles_total.inc()
        return self._step_fn

    def _spec(self):
        """Jitted speculative round over ALL slots: the draft proposes
        ``spec_len`` tokens per row (a scan of single-token continuous
        steps; one extra step commits the last proposal's k/v), the
        target verifies carried-token + proposals in ONE multi-token
        continuous forward (``_attend_decode_continuous``'s chunk
        branch — the chunked verification path), and per-row
        first-mismatch acceptance emits 1..spec_len+1 tokens. Both
        caches then roll back PER ROW to the accepted boundary
        (``_shift_decode_pos``); the rejected writes sit behind the
        position mask until the next round overwrites them. Greedy ids
        are argmax+1 — exactly ``sample_token(greedy=True)`` — so the
        accepted stream is bit-identical to the non-speculative path."""
        if self._spec_fn is None:
            target = self.model
            draft = self.draft
            k = self.spec_len

            def run(params, bufs, d_params, d_bufs, toks):
                def propose(carry, _):
                    db, tok = carry
                    lp, db = functional_apply(
                        draft, d_params, db,
                        tok[:, None].astype(jnp.float32), training=False)
                    nxt = (jnp.argmax(lp[:, -1], axis=-1)
                           + 1).astype(jnp.int32)
                    return (db, nxt), nxt

                # k+1 draft steps: step i consumes proposal i-1; the
                # final step's OUTPUT is discarded but its input write
                # commits proposal k's k/v (kept on acceptance, rolled
                # back with everything else on rejection)
                (d_bufs, _), props = jax.lax.scan(
                    propose, (d_bufs, toks), None, length=k + 1)
                d_props = props[:k].T                      # (slots, k)
                chunk = jnp.concatenate([toks[:, None], d_props], axis=1)
                lp, bufs = functional_apply(
                    target, params, bufs, chunk.astype(jnp.float32),
                    training=False)
                g = (jnp.argmax(lp, axis=-1) + 1).astype(jnp.int32)
                match = d_props == g[:, :k]
                # first mismatch per row; k when the whole draft matched
                # (the appended False column is argmin's sentinel)
                n_acc = jnp.argmin(jnp.concatenate(
                    [match, jnp.zeros((match.shape[0], 1), bool)],
                    axis=1).astype(jnp.int32), axis=1)
                bonus = jnp.take_along_axis(g, n_acc[:, None],
                                            axis=1)[:, 0]
                ar = jnp.arange(k + 1)[None, :]
                props_pad = jnp.concatenate(
                    [d_props, jnp.zeros((d_props.shape[0], 1),
                                        jnp.int32)], axis=1)
                emit = jnp.where(ar < n_acc[:, None], props_pad,
                                 bonus[:, None])
                n_emit = n_acc + 1
                # both models advanced decode_pos by k+1; roll each row
                # back to its own accepted boundary
                delta = n_emit - (k + 1)
                bufs = _shift_decode_pos(bufs, delta)
                d_bufs = _shift_decode_pos(d_bufs, delta)
                return emit, n_emit, bonus, bufs, d_bufs

            self._spec_fn = tracked_jit(run, site="serving.spec_step",
                                        registry=self.registry,
                                        donate_argnums=(1, 3))
            self._tm.serving_recompiles_total.inc()
        return self._spec_fn

    def _spec_round(self):
        """Dispatch one speculative round with the TARGET heads in
        all-positions mode — a trace-time flag (only the FIRST call per
        signature traces, but flipping around every dispatch is a few
        attribute writes). The draft heads stay last-sliced: its scan
        steps are single-token."""
        for h in self._heads:
            h._decode_all = True
        try:
            emit, n_emit, cur, bufs, d_bufs = self._spec()(
                self.params, self.buffers, self.d_params, self.d_buffers,
                jnp.asarray(self._last_tok))
        finally:
            for h in self._heads:
                h._decode_all = False
        return (np.asarray(emit), np.asarray(n_emit),
                np.asarray(cur).astype(np.int32), bufs, d_bufs)

    def _restore_handoff(self, blob: bytes):
        """Admit a SHIPPED prefill partition (disaggregation's decode
        half): deserialize, validate the leaf shapes against this
        server's own template, and merge with the LOCAL statics — model
        weights are identical across replicas of one build, so only the
        per-request partition ever travels."""
        lp, state = deserialize_prefill_state(blob)
        pipe = self._pipeline
        if len(state) != len(pipe.state0):
            raise ValueError(
                f"handoff partition has {len(state)} leaves; this "
                f"server's prefill template has {len(pipe.state0)}")
        for i, (got, want) in enumerate(zip(state, pipe.state0)):
            if got.shape != want.shape:
                raise ValueError(
                    f"handoff leaf {i} has shape {got.shape}, template "
                    f"expects {want.shape} (mismatched prefill mode/"
                    f"chunk between prefill and decode replicas?)")
        return lp, pipe.merge(state, pipe.statics)

    # --------------------------------------------------------------- worker
    def _admit(self, req: _Request) -> bool:
        # the CONTEXT the caches must hold: the prompt plus any resumed
        # cursor prefix (a migrated request re-prefills both — that
        # deterministic replay is what keeps greedy outputs bit-exact)
        plen = len(req.ids) + len(req.emitted0)
        t_admit = req.timing.admitted = time.perf_counter()
        # queue-wait attribution: the retrodicted submit->admission span
        # plus an instant on the request's async lane, both under its rid
        tracing.complete_event("serving.queue_wait", req.t_submit, t_admit,
                               rid=req.rid)
        try:
            with span("serving.prefill", plen=plen, rid=req.rid,
                      mode=self.prefill_mode):
                if req.state_blob is not None:
                    lp, small = self._restore_handoff(req.state_blob)
                    d_small, hit = None, 0
                else:
                    lp, small, d_small, hit = self._run_prefill(
                        req.ids + req.emitted0)
                # key advances per ADMISSION (not per completion — several
                # admits can happen between completions, and identical
                # prompts sampled under a reused key would correlate
                # perfectly)
                self._n_admitted += 1
                key = jax.random.fold_in(self._admit_key, self._n_admitted)
                tok = int(sample_token(lp, key, **self.sampling)[0])
            # the first token is on the host: what a streaming client
            # would see now, before the insert
            req.timing.first_token = time.perf_counter()
            req.timing.token_times.append(req.timing.first_token)
            tracing.async_instant("serving.request", req.rid,
                                  phase="first_token")
            # peek, insert, THEN pop: an insert failure must not leak the
            # slot. (The insert donates self.buffers; a RUNTIME failure
            # mid-insert can still invalidate them — compile-time errors,
            # the common case, happen before donation.) The device-side
            # insert runs OUTSIDE the state lock.
            with self._state_lock:
                slot = self._free[-1]
            with span("serving.insert", slot=slot, rid=req.rid):
                self.buffers = self._insert()(
                    self.buffers, small, jnp.int32(slot), jnp.int32(plen))
                if d_small is not None:
                    # the draft cache needs the prompt too (same wrapper,
                    # second signature); its decode_pos lands on the same
                    # plen so both models enter the round at position P
                    self.d_buffers = self._insert()(
                        self.d_buffers, d_small, jnp.int32(slot),
                        jnp.int32(plen))
            with self._state_lock:
                self._free.pop()
            tracing.async_instant("serving.request", req.rid,
                                  phase="admitted", slot=slot)
            # admission grows the live KV footprint — one of the two
            # watermark sampling points (the other is the step boundary)
            sample_device_memory(self.registry)
            # first token sampled == time-to-first-token for this request
            ttft = time.perf_counter() - req.t_submit
            self._tm.serving_ttft_seconds.observe(ttft)
            if self.prefix_cache_enabled:
                # the hit/miss TTFT split is the prefix cache's headline
                # effect — p50(hit) / p50(miss) is the scoreboard column
                (self._tm.serving_ttft_hit_seconds if hit
                 else self._tm.serving_ttft_miss_seconds).observe(ttft)
            self._tm.serving_admissions_total.inc()
            self._tm.serving_tokens_total.inc()
            sl = _Slot(req)
            sl.emitted = list(req.emitted0) + [tok]
            sl.new_count = len(req.emitted0) + 1
            self._last_tok[slot] = tok
            if self._finish_if_done(slot, sl):
                return True
            with self._state_lock:
                self._active[slot] = sl
            self._tm.serving_slots_occupied.set(len(self._active))
            return True
        except Exception as e:  # noqa: BLE001 — fail the one request
            req.error = f"{type(e).__name__}: {e}"
            req.done.set()
            tracing.async_end("serving.request", req.rid, error=req.error)
            self._tm.serving_request_errors_total.inc()
            return False

    def _finish_if_done(self, slot: int, sl: _Slot) -> bool:
        eos = self.eos_id
        hit_eos = eos is not None and sl.emitted and sl.emitted[-1] == eos
        if hit_eos or sl.new_count >= sl.req.max_new:
            sl.req.result = sl.emitted[:sl.req.max_new]
            sl.req.timing.done = time.perf_counter()
            sl.req.done.set()
            tracing.async_end("serving.request", sl.req.rid,
                              tokens=len(sl.req.result))
            self._n_served += 1
            self._tm.serving_requests_completed_total.inc()
            self._tm.serving_request_latency_seconds.observe(
                time.perf_counter() - sl.req.t_submit)
            with self._state_lock:
                if slot in self._active:
                    del self._active[slot]
                self._free.append(slot)
            self._tm.serving_slots_occupied.set(len(self._active))
            return True
        return False

    def _fail_handoff(self, req: _Request, emitted: List[int],
                      message: str, kind: str) -> None:
        """Fail one request WITH its resume cursor: the host-side prompt
        + emitted tokens survive any device-state loss, so even a dead
        replica's accepted requests leave with everything a peer needs
        to finish them bit-identically (greedy). Skips requests that
        already completed or failed — a second sweeper must not
        overwrite the first one's verdict (or a delivered result)."""
        if req.done.is_set():
            return
        req.handoff = HandoffCursor(ids=list(req.ids),
                                    emitted=list(emitted),
                                    max_new=req.max_new)
        req.fail_kind = kind
        req.error = message
        req.done.set()
        tracing.async_end("serving.request", req.rid, error=message)

    def _sweep_stranded(self) -> None:
        """Snapshot-and-clear every in-flight slot and queued request,
        then fail them — with handoff cursors when the server is
        draining (migration), plain errors on an ordinary close. Shared
        by ``close()``, ``drain()`` and the worker's stop-path (each
        side may run it; the snapshot under ``_state_lock`` guarantees
        every request is failed at most once)."""
        with self._state_lock:
            stranded = list(self._active.items())
            self._active.clear()
            self._free.extend(s for s, _ in stranded)
        queued = drain_queue(self._queue)
        draining = self._draining
        if draining is not None:
            msg = f"server draining: {draining}"
            for _s, sl in stranded:
                self._fail_handoff(sl.req, sl.emitted, msg, "draining")
            for req in queued:
                self._fail_handoff(req, req.emitted0, msg, "draining")
        else:
            fail_requests([sl.req for _s, sl in stranded],
                          "server closed mid-generation",
                          category="serving.request")
            fail_requests(queued,
                          "server closed before the request was dispatched",
                          category="serving.request")
        self._tm.serving_slots_occupied.set(0)
        self._tm.serving_queue_depth.set(0)

    def _die(self, reason: str) -> None:
        """Dead-server state (ADVICE medium, ROADMAP #1): fail every
        in-flight AND queued request NOW, mark the server dead so later
        ``submit()`` calls raise immediately instead of queueing against a
        worker that will never serve them. Never cleared — a decode-step
        failure invalidates the donated cache buffers, so the only safe
        continuation is a new server. Every failed request still leaves
        with its ``HandoffCursor`` (the cursor is host-side state): a
        router re-dispatches it to a healthy peer and the kill loses
        zero accepted requests."""
        self._dead = reason
        self._tm.serving_request_errors_total.inc(len(self._active))
        with self._state_lock:
            stranded = list(self._active.items())
            self._active.clear()
            self._free.extend(slot for slot, _ in stranded)
        for _s, sl in stranded:
            self._fail_handoff(sl.req, sl.emitted,
                               f"server died: {reason}", "dead")
        self._tm.serving_slots_occupied.set(0)
        queued = drain_queue(self._queue)
        for req in queued:
            self._fail_handoff(req, req.emitted0,
                               f"server is dead: {reason}", "dead")
        self._tm.serving_request_errors_total.inc(len(queued))
        self._tm.serving_queue_depth.set(0)

    def _run(self):
        try:
            self._run_loop()
        except Exception as e:  # noqa: BLE001 — the worker-thread boundary
            # an unexpected worker-loop error must not strand clients on
            # their timeouts: declare the server dead and fail everyone
            self._die(f"{type(e).__name__}: {e}")

    def _run_loop(self):
        self._serve_loop()
        # stop-path sweep ON THE WORKER (mirrors close()/drain()): the
        # client-side sweep runs after a BOUNDED join, so on a timed-out
        # join this loop may have admitted or dequeued a request after
        # it — fail the leftovers here so nobody waits out a client
        # timeout, whichever side runs last
        self._sweep_stranded()

    def _serve_loop(self):
        while not self._stop.is_set():
            # strict-FIFO admission into free slots (starvation-free)
            while self._free:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                self._admit(req)
            # refresh AFTER the drain, every pass — a gauge written only
            # on submit would stay stale (showing phantom backlog) once a
            # failed admission or an idle loop empties the queue
            self._tm.serving_queue_depth.set(self._queue.qsize())
            if not self._active:
                try:
                    req = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                self._admit(req)
                continue
            # one decode round for every slot (dead rows compute garbage):
            # a decode_block scan of single-token steps, or in speculative
            # mode one draft+verify round emitting 1..spec_len+1 tokens
            # per row
            self._steps += 1
            counts = None           # spec mode: per-row emit counts
            try:
                for inj in self._chaos:
                    # serving-plane injectors: a raising hook (the
                    # kill-replica drill) lands in the except below and
                    # drives the REAL die path mid-stream; a sleeping
                    # hook (delay-decode) stretches exactly one block
                    inj.on_decode_block(self)
                t_block = time.perf_counter()
                with span("serving.decode_block",
                          live=len(self._active)) as sp:
                    if tracing.is_enabled():
                        # which requests this block advanced (rid linkage;
                        # list built only when the tracer is on)
                        sp.annotate(rids=[sl.req.rid
                                          for sl in self._active.values()])
                    if self.draft is not None:
                        (toks, counts, cur,
                         self.buffers, self.d_buffers) = self._spec_round()
                    else:
                        key = jax.random.fold_in(self._step_key,
                                                 self._steps)
                        toks, self.buffers = self._step()(
                            self.params, self.buffers,
                            jnp.asarray(self._last_tok), key)
                        toks = np.asarray(toks)
            except Exception as e:  # noqa: BLE001 — fail fast AND dead
                # a decode-step failure fails every in-flight request NOW
                # (clients see the error instead of hanging to their
                # timeout) and marks the server DEAD: the step donated
                # self.buffers, so the cache state is gone — "keep
                # admitting" (the PR-5 behaviour) only converted every
                # later request into a slower failure. submit() now raises
                # immediately (ADVICE medium finding, serving.py:302).
                self._die(f"decode step failed: {type(e).__name__}: {e}")
                return
            # the block's tokens are on the host (np.asarray was the sync):
            # the one clock read every token of the block is timed by
            t_host = time.perf_counter()
            live = list(self._active.keys())
            # per-token latency: round wall-clock (np.asarray is the host
            # sync) amortized over the tokens the round produced — fixed
            # decode_block, or the measured mean emit count of live rows
            # in speculative mode (the acceptance rate is what makes the
            # round worth its dispatch)
            per_round = (self.decode_block if counts is None
                         else float(np.mean(counts[live])))
            self._tm.serving_token_latency_seconds.observe(
                (t_host - t_block) / per_round)
            self._tm.serving_decode_blocks_total.inc()
            if counts is not None:
                # each live row was proposed spec_len draft tokens and
                # accepted counts-1 of them (the +1 is the target's own
                # bonus token, not a draft acceptance)
                self._tm.spec_proposed_tokens_total.inc(
                    self.spec_len * len(live))
                self._tm.spec_accepted_tokens_total.inc(
                    int(counts[live].sum()) - len(live))
            sample_device_memory(self.registry)
            self._last_tok = (cur if counts is not None
                              else toks[:, -1].astype(np.int32))
            eos = self.eos_id
            live_tokens = 0
            emitted = []            # tokens each live request got, in order
            for slot, sl in list(self._active.items()):
                row = (toks[slot] if counts is None
                       else toks[slot][:counts[slot]])
                n0 = sl.new_count
                for t in row:
                    t = int(t)
                    sl.emitted.append(t)
                    sl.new_count += 1
                    if ((eos is not None and t == eos)
                            or sl.new_count >= sl.req.max_new):
                        break
                n = sl.new_count - n0
                live_tokens += n
                emitted.append(n)
                sl.req.timing.token_times.extend([t_host] * n)
                self._finish_if_done(slot, sl)
            # beside rids, in their order (late: ring buffer only)
            sp.annotate(tokens=emitted)
            if live_tokens:
                self._tm.serving_tokens_total.inc(live_tokens)
