"""Synthetic-data throughput harness (reference
``models/utils/DistriOptimizerPerf.scala:32`` / ``LocalOptimizerPerf.scala``:
inception/vgg mains with constant|random input, records/s per iteration).

    python -m bigdl_tpu.apps.perf --model inception_v1 -b 32 -i 20
    python -m bigdl_tpu.apps.perf --model resnet50 --distributed  # mesh DP

``--distributed`` shards the batch over every visible device through
DistriOptimizer (the reference's Perf main runs through DistriOptimizer the
same way); default runs the single-chip LocalOptimizer path.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


_LSTM_VOCAB = 20_000
_TRANSFORMER_VOCAB = 32_000


def _build_model(name: str, fused_head: bool = True, moe_experts: int = 0):
    """(model, feature_shape, n_classes, int_vocab, seq_labels) —
    ``int_vocab > 0`` marks integer token-index features (LSTM text
    classification); ``seq_labels`` marks per-timestep targets scored
    with the fused LM-head criterion (default) or
    TimeDistributedCriterion(ClassNLL) with ``fused_head=False`` (the
    causal LM)."""
    from bigdl_tpu.models import (inception, lenet, resnet, rnn, transformer,
                                  vgg, vit)
    builders = {
        "inception_v1": lambda: (inception.build(1000), (224, 224, 3), 1000,
                                 0, False),
        "inception_v2": lambda: (inception.build_v2(1000), (224, 224, 3),
                                 1000, 0, False),
        "vgg16": lambda: (vgg.build_imagenet(1000, depth=16), (224, 224, 3),
                          1000, 0, False),
        "vgg19": lambda: (vgg.build_imagenet(1000, depth=19), (224, 224, 3),
                          1000, 0, False),
        "resnet50": lambda: (resnet.build(1000, depth=50), (224, 224, 3),
                             1000, 0, False),
        "lenet5": lambda: (lenet.build(10), (28, 28, 1), 10, 0, False),
        "vit_s16": lambda: (vit.build(1000), (224, 224, 3), 1000, 0, False),
        "lstm": lambda: (rnn.build_classifier(_LSTM_VOCAB, 128, 128, 20),
                         (500,), 20, _LSTM_VOCAB, False),
        "transformer": lambda: (transformer.build_lm(
            _TRANSFORMER_VOCAB, 256, 8, 1024, num_layers=4, max_len=2048,
            fused_head=fused_head),
            (512,), _TRANSFORMER_VOCAB, _TRANSFORMER_VOCAB, True),
        # realistic-scale LMs (GPT-2-small / GPT-2-medium shaped): big
        # matmuls put the MXU in charge
        "transformer_134m": lambda: (transformer.build_lm(
            _TRANSFORMER_VOCAB, 768, 12, 3072, num_layers=12, max_len=1024,
            fused_head=fused_head),
            (1024,), _TRANSFORMER_VOCAB, _TRANSFORMER_VOCAB, True),
        "transformer_368m": lambda: (transformer.build_lm(
            _TRANSFORMER_VOCAB, 1024, 16, 4096, num_layers=24, max_len=1024,
            fused_head=fused_head),
            (1024,), _TRANSFORMER_VOCAB, _TRANSFORMER_VOCAB, True),
        # billion-scale Llama-recipe configs (GQA 2:1, RoPE, RMSNorm,
        # SwiGLU, tied embeddings, s=2048): the one-chip capacity proof.
        # Run with --optim adamw --optStateDtype bf16 --remat block
        # (fp32 Adam moments alone are 8 GB/B-params — past one v5e).
        "transformer_830m": lambda: (transformer.build_lm(
            _TRANSFORMER_VOCAB, 2048, 16, 5632, num_layers=16, max_len=2048,
            num_kv_heads=8, rope=True, activation="swiglu", norm="rms",
            tie_embeddings=True),
            (2048,), _TRANSFORMER_VOCAB, _TRANSFORMER_VOCAB, True),
        "transformer_1b": lambda: (transformer.build_lm(
            _TRANSFORMER_VOCAB, 2048, 16, 5632, num_layers=20, max_len=2048,
            num_kv_heads=8, rope=True, activation="swiglu", norm="rms",
            tie_embeddings=True),
            (2048,), _TRANSFORMER_VOCAB, _TRANSFORMER_VOCAB, True),
    }
    if name not in builders:
        raise SystemExit(f"unknown model {name}; one of {sorted(builders)}")
    if moe_experts:
        if not name.startswith("transformer"):
            raise SystemExit("--moeExperts applies to transformer models")
        import functools
        from bigdl_tpu.models import transformer as _t
        orig = _t.build_lm
        _t.build_lm = functools.partial(orig, moe_experts=moe_experts)
        try:
            out = builders[name]()
        finally:
            _t.build_lm = orig
        return out
    return builders[name]()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="bigdl_tpu.apps.perf")
    ap.add_argument("--model", "-m", default="inception_v1")
    ap.add_argument("--batchSize", "-b", type=int, default=32)
    ap.add_argument("--iteration", "-i", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--dataType", choices=("constant", "random"),
                    default="random")
    ap.add_argument("--precision", choices=("fp32", "bf16"), default="bf16")
    ap.add_argument("--distributed", action="store_true",
                    help="DistriOptimizer over all visible devices")
    ap.add_argument("--optim", choices=("sgd", "adamw"), default="sgd",
                    help="adamw: the transformer-LM optimizer (lr 1e-4)")
    ap.add_argument("--optStateDtype", choices=("fp32", "bf16"),
                    default="fp32",
                    help="adamw only: moment storage dtype (bf16 halves "
                    "optimizer-state HBM; math stays fp32)")
    ap.add_argument("--remat", choices=("none", "full", "block"),
                    default="none",
                    help="activation rematerialization policy "
                    "(block = per-transformer-block, the LM memory knob)")
    ap.add_argument("--memStats", action="store_true",
                    help="print device memory_stats after the run (HBM "
                    "accounting for capacity studies)")
    ap.add_argument("--moeExperts", type=int, default=0,
                    help="transformer models: top-k routed MoE FFN with "
                    "this many experts (gelu models only)")
    ap.add_argument("--no-fused-head", action="store_true",
                    help="LM only: unfused TimeDistributed(Linear)+LogSoftMax"
                    " tail + ClassNLL instead of LMHead+FusedLMHeadCriterion")
    ap.add_argument("--no-device-cache", action="store_true",
                    help="re-stack + re-transfer batches every epoch instead "
                    "of the device-resident cache (measures the host data "
                    "path; see PERF.md round 3)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.ops.precision import DtypePolicy
    from bigdl_tpu.utils.logger_filter import redirect_logs

    redirect_logs()
    model, shape, n_class, int_vocab, seq_labels = _build_model(
        args.model, fused_head=not args.no_fused_head,
        moe_experts=args.moeExperts)

    rng = np.random.RandomState(0)
    n_records = args.batchSize * 2
    if args.dataType == "constant":
        feats = [np.ones(shape, np.float32) for _ in range(n_records)]
    elif int_vocab:  # 1-based token indices (LookupTable input)
        feats = [rng.randint(1, int_vocab + 1, shape).astype(np.float32)
                 for _ in range(n_records)]
    else:
        feats = [rng.randn(*shape).astype(np.float32)
                 for _ in range(n_records)]
    if seq_labels:  # per-timestep targets (causal LM next-token loss)
        samples = [Sample(f, rng.randint(1, n_class + 1,
                                         shape).astype(np.float32))
                   for f in feats]
    else:
        samples = [Sample(f, np.float32(rng.randint(1, n_class + 1)))
                   for f in feats]
    n_dev = len(jax.devices())
    if args.distributed and args.batchSize % n_dev != 0:
        print(f"note: batch {args.batchSize} does not divide by "
              f"{n_dev} devices; using the host collate path (the sharded "
              "cache needs divisible batches)", file=sys.stderr)
        args.no_device_cache = True
    if args.no_device_cache:
        ds = DataSet.array(samples, distributed=args.distributed).transform(
            SampleToBatch(batch_size=args.batchSize))
    else:
        # device-resident cache (reference CachedDistriDataSet semantics:
        # samples cached once, only indexes reshuffle per epoch) — the host
        # stack + H2D path otherwise dominates on slow-transfer backends;
        # bf16 runs cache in bf16 (half the one-time transfer + footprint).
        # Distributed runs shard the cache over the data axis
        # (DistriOptimizer injects its mesh; per-shard reshuffle).
        from bigdl_tpu.dataset import DeviceCachedDataSet
        ds = DeviceCachedDataSet(
            DataSet.array(samples, distributed=args.distributed),
            batch_size=args.batchSize,
            cast_dtype="bfloat16" if (args.precision == "bf16"
                                      and not int_vocab) else None)

    if seq_labels:
        criterion = (nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
                     if args.no_fused_head else nn.FusedLMHeadCriterion())
    else:
        criterion = nn.ClassNLLCriterion()
    if args.distributed:
        from bigdl_tpu.parallel import MeshTopology
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
        opt = DistriOptimizer(model, ds, criterion,
                              topology=MeshTopology.data_parallel())
    else:
        from bigdl_tpu.optim import Optimizer
        opt = Optimizer(model, ds, criterion)
    if args.optim == "adamw":
        from bigdl_tpu.optim import AdamW
        opt.set_optim_method(AdamW(
            learningrate=1e-4,
            state_dtype="bfloat16" if args.optStateDtype == "bf16" else None))
    else:
        opt.set_optim_method(SGD(learningrate=0.01))
    if args.remat != "none":
        opt.set_remat(True if args.remat == "full" else args.remat)
    if args.precision == "bf16":
        opt.set_precision(DtypePolicy.bf16())
    total_iters = args.warmup + args.iteration

    class _Recorder:
        """Minimal TrainSummary-shaped sink capturing per-iteration
        Throughput so the steady-state rate can exclude the first
        ``warmup`` (compile-dominated) iterations."""
        def __init__(self):
            self.throughputs = []

        def add_scalar(self, tag, value, step):
            if tag == "Throughput":
                self.throughputs.append(float(value))

        def get_summary_trigger(self, name):
            return None

    recorder = _Recorder()
    opt.set_train_summary(recorder)
    opt.set_end_when(Trigger.max_iteration(total_iters))

    t0 = time.time()
    opt.optimize()
    wall = time.time() - t0
    if args.memStats:
        stats = jax.local_devices()[0].memory_stats() or {}
        print(json.dumps({"memory_stats": {
            k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit", "largest_alloc_size")
            if k in stats}}), file=sys.stderr)
    steady = recorder.throughputs[args.warmup:]
    print(json.dumps({
        "harness": "perf", "model": args.model, "batch": args.batchSize,
        "iterations": args.iteration, "wall_s": round(wall, 3),
        "records_per_sec": round(float(np.mean(steady)), 1) if steady else 0.0,
        "records_per_sec_incl_compile":
            round(total_iters * args.batchSize / wall, 1),
        "devices": len(jax.devices()),
        "distributed": bool(args.distributed),
        "precision": args.precision,
    }))


if __name__ == "__main__":
    main()
