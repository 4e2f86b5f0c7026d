"""Causal-LM train + generate mains for the long-context transformer stack
(new capability; CLI shape mirrors the other ``Train.scala``-style mains).

    python -m bigdl_tpu.apps.transformer train -b 8 --seqLen 256 -e 2
    python -m bigdl_tpu.apps.transformer train --contextParallel ring
    python -m bigdl_tpu.apps.transformer generate --model ckpt.bigdl \
        --prompt 3,5,7 --maxNewTokens 32 --topK 40

``--contextParallel`` shards the sequence axis of every attention layer over
the mesh (ring attention or Ulysses) — the exact capability SURVEY §5.7
requires that the reference lacks.
"""

from __future__ import annotations

import sys

import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.apps.common import build_optimizer, train_parser
from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
from bigdl_tpu.models import transformer
from bigdl_tpu.utils import file_io


def _synthetic_corpus(n: int, seq_len: int, vocab: int, seed: int = 17):
    """Next-token samples over a learnable synthetic grammar: token t+1 is a
    fixed affine map of token t plus noise, so a real LM beats uniform."""
    rng = np.random.RandomState(seed)
    samples = []
    for _ in range(n):
        toks = np.empty(seq_len + 1, np.int64)
        toks[0] = rng.randint(1, vocab + 1)
        for t in range(seq_len):
            nxt = (toks[t] * 31 + 7) % vocab + 1
            toks[t + 1] = nxt if rng.rand() < 0.9 \
                else rng.randint(1, vocab + 1)
        samples.append(Sample(toks[:-1].astype(np.float32),
                              toks[1:].astype(np.float32)))
    return samples


def _text_corpus(args):
    """BPE-tokenize ``--textFile`` into next-token samples; the learned
    tokenizer is saved beside the checkpoint so ``generate --tokenizer``
    can decode real text."""
    from bigdl_tpu.dataset.bpe import BPETokenizer
    if args.bpeVocab < 256:
        raise SystemExit("--bpeVocab must be >= 256 (the byte alphabet)")
    with open(args.textFile, encoding="utf-8") as f:
        lines = [ln.rstrip("\n") for ln in f if ln.strip()]
    tok = BPETokenizer.train(lines, vocab_size=args.bpeVocab)
    stream = []
    for ln in lines:
        stream.extend(tok.encode(ln) + [tok.eos_id])
    s = args.seqLen
    samples = [Sample(np.asarray(stream[i:i + s], np.float32),
                      np.asarray(stream[i + 1:i + 1 + s], np.float32))
               for i in range(0, len(stream) - s, s)]
    if not samples:
        raise SystemExit(f"--textFile too small for --seqLen {s} "
                         f"({len(stream)} tokens)")
    if args.checkpoint:
        import os as _os
        _os.makedirs(args.checkpoint, exist_ok=True)
        tok.save(f"{args.checkpoint}/tokenizer.bigdl")
    print(f"text corpus: {len(stream)} tokens, BPE vocab {tok.vocab_size} "
          f"(+eos {tok.eos_id}), {len(samples)} samples", file=sys.stderr)
    return samples, tok.eos_id


def train(argv):
    parser = train_parser("bigdl_tpu.apps.transformer train",
                          default_batch=8, default_epochs=2, default_lr=3e-3)
    parser.add_argument("--seqLen", type=int, default=128)
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--embedDim", type=int, default=64)
    parser.add_argument("--numHeads", type=int, default=4)
    parser.add_argument("--numLayers", type=int, default=2)
    parser.add_argument("--contextParallel", default=None,
                        choices=[None, "ring", "ulysses"],
                        help="shard the sequence axis over the mesh")
    parser.add_argument("--moeExperts", type=int, default=0,
                        help="replace the FFN with a top-2 routed MoE of "
                        "this many experts (0 = dense)")
    parser.add_argument("--tensorParallel", type=int, default=1,
                        help="Megatron TP degree (dp x tp mesh); adds "
                        "sequence-parallel regions when seqLen divides")
    parser.add_argument("--ringLayout", default="contiguous",
                        choices=["contiguous", "zigzag"],
                        help="ring shard layout; zigzag balances causal "
                        "work across devices (ring mode only)")
    parser.add_argument("--fusedHead", action="store_true",
                        help="LMHead + FusedLMHeadCriterion tail: the "
                        "(B,S,V) logits never materialise (plain data-"
                        "parallel path only)")
    parser.add_argument("--llamaBlock", action="store_true",
                        help="Llama-family block recipe: RoPE + RMSNorm + "
                        "SwiGLU (untied log-prob tail, so every training "
                        "mode drives it). Composes with --contextParallel "
                        "(round 5: per-shard global rope positions — the "
                        "long-context training recipe)")
    parser.add_argument("--textFile", default=None,
                        help="train on REAL text: BPE-tokenize this file "
                        "(--bpeVocab merges), save the tokenizer next to "
                        "--checkpoint; --vocab is then derived")
    parser.add_argument("--bpeVocab", type=int, default=512,
                        help="BPE vocab size (>= 256; byte alphabet + "
                        "merges)")
    args = parser.parse_args(argv)

    if args.contextParallel and args.tensorParallel > 1:
        raise SystemExit("--contextParallel and --tensorParallel are "
                         "separate modes; pick one")
    if args.llamaBlock and args.moeExperts:
        raise SystemExit("--llamaBlock (swiglu FFN) does not compose with "
                         "--moeExperts yet")
    if args.fusedHead and (args.contextParallel or args.tensorParallel > 1):
        raise SystemExit("--fusedHead composes with the plain data-"
                         "parallel path only")
    if args.textFile:
        samples, args.vocab = _text_corpus(args)
    else:
        samples = _synthetic_corpus(max(args.synthetic_size, args.batchSize),
                                    args.seqLen, args.vocab)
    ds = DataSet.array(samples,
                       distributed=args.tensorParallel > 1).transform(
        SampleToBatch(batch_size=args.batchSize))

    llama_kwargs = (dict(rope=True, norm="rms", activation="swiglu",
                         bias=False)
                    if args.llamaBlock else {})
    model = transformer.build_lm(
        args.vocab, args.embedDim, args.numHeads, ffn_dim=4 * args.embedDim,
        num_layers=args.numLayers, max_len=max(1024, args.seqLen),
        seq_axis="seq" if args.contextParallel else None,
        seq_mode=args.contextParallel or "ring",
        seq_layout=args.ringLayout if args.contextParallel == "ring"
        else "contiguous",
        moe_experts=args.moeExperts,
        fused_head=args.fusedHead, **llama_kwargs)
    if args.fusedHead:
        criterion = nn.FusedLMHeadCriterion()
    else:
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())

    if args.contextParallel:
        if bool(args.model) != bool(args.state):
            raise SystemExit("--model and --state must be passed together")
        trained = _train_context_parallel(model, criterion, ds, args)
    elif args.tensorParallel > 1:
        # dp x tp mesh through the standard Optimizer path: Megatron specs
        # are inferred per layer; SP regions shard the norm/dropout
        # segments when the sequence divides the tp degree
        import jax
        from bigdl_tpu.parallel.mesh import MeshTopology
        from bigdl_tpu.parallel.tensor_parallel import \
            enable_sequence_parallel
        n = len(jax.devices())
        tp = args.tensorParallel
        if n % tp != 0:
            raise SystemExit(f"--tensorParallel {tp} must divide the "
                             f"device count {n}")
        topo = MeshTopology(data=n // tp, tensor=tp)
        if args.seqLen % tp == 0:
            enable_sequence_parallel(model, topo.build())
        opt = build_optimizer(model, ds, criterion, args, topology=topo)
        trained = opt.optimize()
    else:
        opt = build_optimizer(model, ds, criterion, args)
        trained = opt.optimize()
    if args.checkpoint:
        file_io.save(trained, f"{args.checkpoint}/model_final")
    return trained


def _train_context_parallel(model, criterion, ds, args):
    """Sequence-parallel SPMD loop. Split by position-dependence:

    - embedding + positional encoding run GLOBALLY (a PE inside shard_map
      would stamp every shard with positions 0..S/P-1);
    - the attention stack + LM head + criterion run inside ``shard_map``
      over the mesh ``seq`` axis so ring/Ulysses collectives have their
      axis bound, with the per-shard loss ``pmean``-ed (without it the
      shard_map transpose psums gradients P times too large).

    Checkpoint/resume rides the resilience coordinator
    (``bigdl_tpu/resilience``): ``--checkpoint`` writes per-epoch
    (model.N, state.N) pairs + RESUME markers, ``--model/--state`` (or
    ``--autoResume``) restores params/optimizer/epoch counters — from a
    cp-format pair, OR from a full-model snapshot written by the standard
    Optimizer loop (plain or sharded; the param tree is re-split into the
    embed/tail halves). TensorBoard summaries remain unwired here.
    """
    import logging

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel.mesh import MeshTopology

    log = logging.getLogger("bigdl_tpu.optim")
    if args.summary:
        log.warning("--summary is ignored with --contextParallel")
    n = len(jax.devices())
    if args.seqLen % n != 0:
        raise SystemExit(
            f"--seqLen {args.seqLen} is not divisible by the device count "
            f"{n}: sequence parallelism shards the sequence axis evenly "
            "across devices; pick a multiple")
    zigzag = (args.contextParallel == "ring"
              and args.ringLayout == "zigzag")
    if zigzag and args.seqLen % (2 * n) != 0:
        raise SystemExit(
            f"--ringLayout zigzag needs --seqLen divisible by 2x the "
            f"device count ({2 * n})")
    mesh = MeshTopology(sequence=n).build()
    method = SGD(learningrate=args.learningRate,
                 learningrate_decay=args.learningRateDecay,
                 momentum=args.momentum, weightdecay=args.weightDecay)
    # model = [LookupTable, (PositionalEncoding — absent under rope),
    #          TransformerEncoder, TimeDistributed(Linear), LogSoftMax]
    # (models/transformer.py); split at the encoder so both layouts work
    mods = list(model)
    enc_idx = next(i for i, m in enumerate(mods)
                   if isinstance(m, nn.TransformerEncoder))
    embed, tail = nn.Sequential(), nn.Sequential()
    for m in mods[:enc_idx]:
        embed.add(m)
    for m in mods[enc_idx:]:
        tail.add(m)
    params = {"embed": embed.parameter_tree(), "tail": tail.parameter_tree()}
    opt_state = method.init_state(params)

    from bigdl_tpu.resilience import coordinator
    start_epoch, neval = 1, 1
    resume_model, resume_state = args.model, args.state
    if (not resume_model and getattr(args, "autoResume", False)
            and args.checkpoint):
        point = coordinator.latest_resume_point(args.checkpoint)
        if point is not None:
            resume_model, resume_state = point.model_path, point.state_path
            log.info("[AutoResume] discovered snapshot %s", resume_model)
    if resume_model and resume_state:
        state_tpl = jax.eval_shape(method.init_state, params)
        try:  # cp-format pair first ({"embed","tail"} param halves)
            saved_params, saved_state, driver = coordinator \
                .load_snapshot_host(resume_model, resume_state, params,
                                    state_tpl)
        except KeyError:  # a standard-loop snapshot: full model tree
            full_tpl = model.parameter_tree()
            full_state_tpl = jax.eval_shape(method.init_state, full_tpl)
            saved_params, saved_state, driver = coordinator \
                .load_snapshot_host(resume_model, resume_state, full_tpl,
                                    full_state_tpl)
        if isinstance(saved_params, dict) \
                and set(saved_params) == {"embed", "tail"}:
            params = jax.tree_util.tree_map(jnp.asarray, saved_params)
        else:  # full-model tree -> load, then re-split into the halves
            model.load_parameter_tree(
                jax.tree_util.tree_map(jnp.asarray, saved_params))
            params = {"embed": embed.parameter_tree(),
                      "tail": tail.parameter_tree()}
        same_structure = (jax.tree_util.tree_structure(saved_state)
                          == jax.tree_util.tree_structure(opt_state))
        if same_structure:
            opt_state = jax.tree_util.tree_map(jnp.asarray, saved_state)
        else:
            log.warning("optimizer state in %s has a different structure "
                        "(non-cp training mode?); reinitializing it",
                        resume_state)
        start_epoch = int(driver.get("epoch", 1))
        neval = int(driver.get("neval", 1))
        log.info("[Resume] context-parallel from %s at epoch %d neval %d",
                 resume_model, start_epoch, neval)

    def _save_cadence(epoch_done: int) -> None:
        if not args.checkpoint:
            return
        from bigdl_tpu.utils import file_io as fio
        tag = f".{neval}"
        fio.save({"params": params, "buffers": {}},
                 fio.join(args.checkpoint, f"model{tag}"))
        state_path = fio.join(args.checkpoint, f"state{tag}")
        fio.save({"optim": opt_state,
                  "driver": {"epoch": epoch_done + 1, "neval": neval}},
                 state_path)
        coordinator.write_marker(
            state_path, step=neval, epoch=epoch_done + 1,
            rng_key_data=None, rng_seed=0, epoch_batches=0,
            epoch_records=0,
            mesh={"process_count": int(jax.process_count()),
                  "device_count": int(jax.device_count()),
                  "mesh_shape": {"seq": n}, "sync_mode": "context-parallel"},
            cursor_epoch=epoch_done)
        log.info("[Checkpoint] saved model%s to %s", tag, args.checkpoint)

    def tail_loss(p_tail, x_embedded, targets):
        out, _ = functional_apply(tail, p_tail, {}, x_embedded, training=True)
        loss = criterion.apply(out, targets).astype(jnp.float32)
        return jax.lax.pmean(loss, "seq")

    sharded_tail = shard_map(
        tail_loss, mesh=mesh,
        in_specs=(P(), P(None, "seq", None), P(None, "seq")),
        out_specs=P(), check_vma=False)

    if zigzag:
        # Zigzag ring layout: permute the EMBEDDED sequence (positions are
        # already stamped globally) and the targets so the contiguous
        # shard_map split hands device i its (i, 2P-1-i) chunk pair; the
        # mean loss is permutation-invariant, so nothing is un-permuted.
        from bigdl_tpu.parallel.context import zigzag_permutation
        zperm = jnp.asarray(zigzag_permutation(args.seqLen, n))

    def loss_fn(p, tokens, targets):
        x, _ = functional_apply(embed, p["embed"], {}, tokens, training=True)
        if zigzag:
            x = jnp.take(x, zperm, axis=1)
            targets = jnp.take(targets, zperm, axis=1)
        return sharded_tail(p["tail"], x, targets)

    @jax.jit
    def step(p, o, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, targets)
        new_p, new_o = method.update(grads, o, p)
        return new_p, new_o, loss

    for epoch in range(start_epoch, args.maxEpoch + 1):
        ds.shuffle()
        for batch in ds.data(train=True):
            tokens = jnp.asarray(batch.data)
            targets = jnp.asarray(batch.labels)
            params, opt_state, loss = step(params, opt_state,
                                           tokens, targets)
            log.info("[Epoch %d][Iteration %d] loss %.5f (seq-parallel x%d,"
                     " %s)", epoch, neval, float(loss), n,
                     args.contextParallel)
            neval += 1
        _save_cadence(epoch)
    embed.load_parameter_tree(params["embed"])
    tail.load_parameter_tree(params["tail"])
    return model


def generate_cmd(argv) -> None:
    """Sample from a trained (or fresh synthetic-grammar) causal LM."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.apps.transformer generate")
    ap.add_argument("--model", default=None,
                    help="saved model path (file_io); default: train a "
                    "fresh tiny LM on the synthetic grammar first")
    ap.add_argument("--fromHF", default=None, metavar="DIR",
                    help="load a HuggingFace checkpoint directory "
                    "(config.json + safetensors/bin; GPT-2 or Llama "
                    "family) instead of --model. Prompt ids are then "
                    "HF 0-based ids.")
    ap.add_argument("--prompt", default="1,2,3",
                    help="comma-separated 1-based token ids "
                    "(0-based with --fromHF)")
    ap.add_argument("--maxNewTokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--topK", type=int, default=0)
    ap.add_argument("--topP", type=float, default=0.0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--numBeams", type=int, default=0)
    ap.add_argument("--lengthPenalty", type=float, default=1.0)
    ap.add_argument("--eosId", type=int, default=None)
    ap.add_argument("--repetitionPenalty", type=float, default=1.0)
    ap.add_argument("--minNewTokens", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="decode with the int8 weight-only quantized twin "
                    "(footprint knob: 4x smaller resident weights)")
    ap.add_argument("--bf16", action="store_true",
                    help="decode with the bf16 cast twin (latency knob: "
                    "measured 1.69x at 134M/B=1, PERF.md round 4)")
    ap.add_argument("--tokenizer", default=None,
                    help="BPE tokenizer path (from train --textFile): "
                    "--prompt is then TEXT and the continuation prints "
                    "as text")
    args = ap.parse_args(argv)
    if args.int8 and args.bf16:
        raise SystemExit("pick one of --int8 / --bf16")

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.generation import generate

    hf_shift = 0
    if args.fromHF and args.model:
        raise SystemExit("pass --model or --fromHF, not both")
    if args.fromHF and args.tokenizer:
        raise SystemExit("--fromHF does not compose with --tokenizer (a "
                         "framework BPE vocab against an HF checkpoint's "
                         "vocab would decode garbage); pass raw HF ids")
    tok = None
    if args.fromHF:
        from bigdl_tpu.interop.hf import load_hf_checkpoint
        from bigdl_tpu.interop.hf_tokenizer import load_checkpoint_tokenizer
        model = load_hf_checkpoint(args.fromHF)
        if args.eosId is not None:
            args.eosId += 1  # the CLI eos under --fromHF is an HF id
        # checkpoint dir carries its tokenizer (GPT-2 byte-BPE json or
        # Llama sentencepiece tokenizer.model): --prompt is TEXT and
        # encode/decode already speak framework 1-based ids
        try:
            tok = load_checkpoint_tokenizer(args.fromHF)
            print(f"loaded {tok!r} from the checkpoint dir; --prompt "
                  "is text", file=sys.stderr)
        except FileNotFoundError:
            pass
        except ValueError as e:  # present but unreadable
            print(f"checkpoint tokenizer not readable ({e}); falling "
                  "back to raw HF ids", file=sys.stderr)
        if tok is None:
            hf_shift = 1  # HF ids are 0-based; the framework's 1-based
    elif args.model:
        model = file_io.load(args.model)
    else:
        print("no --model given: training a tiny LM on the synthetic "
              "grammar first", file=sys.stderr)
        model = train(["-b", "8", "--seqLen", "32", "--maxEpoch", "1"])
    if args.int8:
        model = nn.quantize_model(model)
    elif args.bf16:
        model = nn.cast_model(model)
    if args.tokenizer:
        from bigdl_tpu.dataset.bpe import BPETokenizer
        tok = BPETokenizer.load(args.tokenizer)
    if tok is not None:
        ids = [float(t) for t in tok.encode(args.prompt)]
        if args.eosId is None:
            args.eosId = tok.eos_id
    else:
        ids = [float(t) + hf_shift
               for t in args.prompt.split(",") if t.strip()]
    if not ids:
        raise SystemExit("empty prompt: pass at least one token (text with "
                         "--tokenizer, else comma-separated 1-based ids); a "
                         "(1, 0) prompt would fail deep in the prefill with "
                         "an opaque shape error")
    prompt = jnp.asarray([ids])
    out = generate(model, prompt, args.maxNewTokens,
                   temperature=args.temperature, top_k=args.topK,
                   top_p=args.topP, greedy=args.greedy,
                   num_beams=args.numBeams,
                   length_penalty=args.lengthPenalty, eos_id=args.eosId,
                   repetition_penalty=args.repetitionPenalty,
                   min_new_tokens=args.minNewTokens,
                   key=jax.random.PRNGKey(args.seed))
    ids = np.asarray(out[0]).astype(int).tolist()  # one host transfer
    if hf_shift:
        ids = [i - hf_shift for i in ids]  # back to HF 0-based ids
    n0 = prompt.shape[1]
    if tok is not None:
        print("prompt:      ", repr(tok.decode(ids[:n0])))
        print("continuation:", repr(tok.decode(ids[n0:])))
    else:
        print("prompt:      ", ids[:n0])
        print("continuation:", ids[n0:])


def serve_cmd(argv) -> None:
    """Batched HTTP serving over the KV-cached decode (``models.lm_server``;
    the reference's udfpredictor/DLClassifier serving quadrant, LM era)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bigdl_tpu.apps.transformer serve")
    ap.add_argument("--model", default=None,
                    help="saved model path (file_io); default: train a "
                    "fresh tiny LM on the synthetic grammar first")
    ap.add_argument("--fromHF", default=None, metavar="DIR",
                    help="serve a HuggingFace checkpoint directory "
                    "(GPT-2/Llama family); clients then speak 1-based "
                    "framework ids (HF id + 1) unless --tokenizer is set")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--maxBatch", type=int, default=8,
                    help="micro-batch cap (requests gathered per dispatch)")
    ap.add_argument("--batchTimeoutMs", type=float, default=20.0,
                    help="how long a dispatch waits for same-length company")
    ap.add_argument("--maxNewTokens", type=int, default=64,
                    help="decode budget per batch (per-request limits trim)")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--topK", type=int, default=0)
    ap.add_argument("--topP", type=float, default=0.0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--eosId", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--int8", action="store_true",
                    help="serve the int8 weight-only quantized twin")
    ap.add_argument("--bf16", action="store_true",
                    help="serve the bf16 cast twin (decode latency knob)")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-scheduled continuous batching (rope models "
                    "only): mixed-length generations share the chip "
                    "instead of lockstep same-length micro-batches")
    ap.add_argument("--slots", type=int, default=8,
                    help="--continuous: concurrent generation slots")
    ap.add_argument("--maxLen", type=int, default=256,
                    help="--continuous: per-slot KV cache length "
                    "(prompt + generation budget)")
    ap.add_argument("--decodeBlock", type=int, default=8,
                    help="--continuous: tokens decoded per dispatch")
    ap.add_argument("--prefillMode", default=None,
                    choices=("chunked", "bucketed"),
                    help="--continuous: O(1)-compile prefill strategy "
                    "(default chunked, or BIGDL_PREFILL_MODE; bucketed "
                    "= pow2 length buckets for attention paths that "
                    "can't take the masked chunk)")
    ap.add_argument("--prefillChunk", type=int, default=None,
                    help="--continuous: chunked-prefill width (default "
                    "128, or BIGDL_PREFILL_CHUNK)")
    ap.add_argument("--draft", default=None, metavar="PATH",
                    help="--continuous: saved draft model path (file_io) "
                    "enabling speculative decode — the draft proposes "
                    "specLen tokens per round, the target verifies in one "
                    "dispatch; greedy-only, outputs bit-identical to "
                    "non-speculative decode")
    ap.add_argument("--specLen", type=int, default=None,
                    help="--continuous --draft: draft tokens proposed per "
                    "round (default 4, or BIGDL_SPEC_LEN)")
    ap.add_argument("--prefixCache", default=None,
                    choices=("on", "off"),
                    help="--continuous: cross-request KV prefix cache "
                    "over chunk-aligned prompt prefixes (default on in "
                    "chunked mode, or BIGDL_PREFIX_CACHE)")
    ap.add_argument("--prefixCacheMB", type=float, default=None,
                    help="--continuous: prefix-cache budget in MiB "
                    "(default 64, or BIGDL_PREFIX_CACHE_MB)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--continuous: in-process serving replicas "
                    "behind the fleet router (models.router.LMRouter); "
                    "a replica dying or draining moves its requests to "
                    "a peer instead of failing them")
    ap.add_argument("--disaggregate", default=None, metavar="P:D",
                    help="--continuous: prefill:decode replica split "
                    "(e.g. 1:2) — admission prefill runs on dedicated "
                    "prefill replicas and ships the serialized state "
                    "partition to decode replicas; overrides --replicas")
    ap.add_argument("--tokenizer", default=None,
                    help="BPE tokenizer path: requests may then POST "
                    '{"text": ...} and responses include decoded text')
    args = ap.parse_args(argv)
    if args.int8 and args.bf16:
        raise SystemExit("pick one of --int8 / --bf16")

    from bigdl_tpu.models.lm_server import LMServer, make_http_server

    if args.fromHF and args.model:
        raise SystemExit("pass --model or --fromHF, not both")
    if args.fromHF and args.tokenizer:
        raise SystemExit("--fromHF does not compose with --tokenizer (a "
                         "framework BPE vocab against an HF checkpoint's "
                         "vocab would decode garbage); the checkpoint "
                         "dir's own tokenizer loads automatically")
    tok = None
    if args.fromHF:
        from bigdl_tpu.interop.hf import load_hf_checkpoint
        from bigdl_tpu.interop.hf_tokenizer import load_checkpoint_tokenizer
        model = load_hf_checkpoint(args.fromHF)
        try:
            tok = load_checkpoint_tokenizer(args.fromHF)
            print(f"serving with {tok!r} from the checkpoint dir",
                  file=sys.stderr)
        except FileNotFoundError:
            pass
        except ValueError as e:  # unreadable: serve raw framework ids
            print(f"checkpoint tokenizer not readable ({e}); clients "
                  "must POST id prompts", file=sys.stderr)
    elif args.model:
        model = file_io.load(args.model)
    else:
        print("no --model given: training a tiny LM on the synthetic "
              "grammar first", file=sys.stderr)
        model = train(["-b", "8", "--seqLen", "32", "--maxEpoch", "1"])
    if args.int8:
        model = nn.quantize_model(model)
    elif args.bf16:
        model = nn.cast_model(model)
    if args.tokenizer:
        from bigdl_tpu.dataset.bpe import BPETokenizer
        tok = BPETokenizer.load(args.tokenizer)
    if tok is not None and args.eosId is None:
        args.eosId = tok.eos_id
    if args.continuous:
        import copy

        from bigdl_tpu.models.serving import ContinuousLMServer
        from bigdl_tpu.resilience.chaos import from_env as chaos_from_env
        from bigdl_tpu.resilience.serving_drill import parse_split
        split = parse_split(args.disaggregate)
        n_decode = split[1] if split else max(1, args.replicas)
        n_prefill = split[0] if split else 0
        if (n_decode + n_prefill > 1) and args.draft:
            raise SystemExit("--draft does not compose with a multi-"
                             "replica fleet (state handoff is "
                             "incompatible with speculative serving)")
        chaos = chaos_from_env()
        draft = file_io.load(args.draft) if args.draft else None

        def mk_server(mdl, slots, chaos_inj):
            return ContinuousLMServer(
                mdl, slots=slots, max_len=args.maxLen,
                decode_block=args.decodeBlock,
                max_new_tokens=args.maxNewTokens,
                temperature=args.temperature, top_k=args.topK,
                top_p=args.topP, greedy=args.greedy,
                eos_id=args.eosId, seed=args.seed,
                prefill_mode=args.prefillMode,
                prefill_chunk=args.prefillChunk,
                draft=draft, spec_len=args.specLen,
                prefix_cache=(None if args.prefixCache is None
                              else args.prefixCache == "on"),
                prefix_cache_mb=args.prefixCacheMB,
                chaos=chaos_inj)

        if n_decode + n_prefill == 1:
            server = mk_server(model, args.slots, chaos)
        else:
            # each replica holds its own decode state, so each needs its
            # own module instance; deepcopies keep the weights
            # bit-identical across the fleet (the handoff contract)
            from bigdl_tpu.models.router import LMRouter
            models = [model] + [copy.deepcopy(model)
                                for _ in range(n_decode + n_prefill - 1)]
            decode = [mk_server(models[i], args.slots,
                                chaos if i == 0 else None)
                      for i in range(n_decode)]
            prefill = [mk_server(models[n_decode + i], 1, None)
                       for i in range(n_prefill)]
            server = LMRouter(decode, prefill_replicas=prefill,
                              chaos=chaos)
            print(f"fleet: {n_decode} decode"
                  + (f" + {n_prefill} prefill" if n_prefill else "")
                  + " replicas behind the router", file=sys.stderr)
    elif args.draft or args.specLen or args.prefixCache:
        raise SystemExit("--draft/--specLen/--prefixCache require "
                         "--continuous")
    elif args.replicas != 1 or args.disaggregate:
        raise SystemExit("--replicas/--disaggregate require --continuous")
    else:
        server = LMServer(model, max_batch=args.maxBatch,
                          batch_timeout_ms=args.batchTimeoutMs,
                          max_new_tokens=args.maxNewTokens,
                          temperature=args.temperature, top_k=args.topK,
                          top_p=args.topP, greedy=args.greedy,
                          eos_id=args.eosId, seed=args.seed)
    httpd = make_http_server(server, args.host, args.port, tokenizer=tok)

    # graceful drain: SIGTERM flips the PreemptionHandler flag; the
    # watcher drains the server/fleet (in-flight requests leave as
    # handoff cursors, /health turns 503 draining) and stops the HTTP
    # loop — the preemption path for a serving process
    import threading as _threading
    import time

    from bigdl_tpu.resilience.preemption import PreemptionHandler
    preempt = PreemptionHandler().install()

    def _watch_preemption():
        while not preempt.should_snapshot():
            time.sleep(0.1)
        reason = preempt.reason or "preemption notice"
        print(f"draining: {reason}", file=sys.stderr)
        drain = getattr(server, "drain", None)
        if drain is not None:
            drain(reason)
        httpd.shutdown()

    _threading.Thread(target=_watch_preemption, daemon=True,
                      name="bigdl-serve-preempt").start()
    print(f"serving on http://{args.host}:{httpd.server_address[1]} "
          f"(POST /generate, GET /health, GET /metrics)", file=sys.stderr)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.shutdown()
        server.close()
        preempt.uninstall()


def main() -> None:
    if len(sys.argv) < 2 or sys.argv[1] not in ("train", "generate",
                                                "serve"):
        raise SystemExit("usage: python -m bigdl_tpu.apps.transformer "
                         "{train|generate|serve} ...")
    if sys.argv[1] == "generate":
        generate_cmd(sys.argv[2:])
    elif sys.argv[1] == "serve":
        serve_cmd(sys.argv[2:])
    else:
        train(sys.argv[2:])


if __name__ == "__main__":
    main()
