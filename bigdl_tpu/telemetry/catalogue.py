"""Well-known metric and span inventory — the single source of truth.

Every instrumented subsystem (serving, training, eval) creates its
families FROM these specs, and ``scripts/gen_api_doc.py`` renders this
table into ``docs/API.md`` — so the docs can never drift from what a
scrape actually returns. Narrative guide: ``docs/OBSERVABILITY.md``.

Bucket choices: serving latencies use the sub-ms-to-seconds default;
training step phases reuse it (a CPU-fallback step is seconds, a TPU
step sub-ms — the shared ladder covers both); batch sizes use power-of-
two buckets matching the bucketed batcher's padding.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

from bigdl_tpu.telemetry.registry import (DEFAULT_LATENCY_BUCKETS,
                                          MetricSpec, MetricsRegistry)

__all__ = ["METRIC_SPECS", "SPAN_SPECS", "SCOPE_SPECS", "ScopeSpec",
           "instruments"]

BATCH_SIZE_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128)

METRIC_SPECS: List[MetricSpec] = [
    # ---- continuous-batching serving engine (models/serving.py)
    MetricSpec("bigdl_serving_ttft_seconds", "histogram",
               "Time to first token: request submit to first sampled token "
               "(prefill + queue wait).", (), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_serving_token_latency_seconds", "histogram",
               "Per-token decode latency, observed once per decode block "
               "as block wall-clock / tokens.", (), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_serving_request_latency_seconds", "histogram",
               "Whole-request latency: submit to completion (one "
               "observation per completed request).",
               (), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_serving_queue_depth", "gauge",
               "Requests waiting for a slot (admission queue)."),
    MetricSpec("bigdl_serving_slots_occupied", "gauge",
               "Slots currently decoding a live request."),
    MetricSpec("bigdl_serving_slots_total", "gauge",
               "Configured slot count of the continuous server."),
    MetricSpec("bigdl_serving_admissions_total", "counter",
               "Requests admitted into a slot (prefill + insert done)."),
    MetricSpec("bigdl_serving_requests_completed_total", "counter",
               "Requests finished (eos or token budget)."),
    MetricSpec("bigdl_serving_request_errors_total", "counter",
               "Requests failed (admission or decode error)."),
    MetricSpec("bigdl_serving_recompiles_total", "counter",
               "New XLA program builds: the O(1) chunked-prefill pair "
               "(or a first-seen pow2 length bucket in bucketed mode), "
               "the step program, the insert program."),
    MetricSpec("bigdl_serving_decode_blocks_total", "counter",
               "Jitted decode blocks dispatched over all slots."),
    MetricSpec("bigdl_serving_tokens_total", "counter",
               "Tokens emitted to live requests (dead-slot lanes "
               "excluded)."),
    MetricSpec("bigdl_serving_ttft_hit_seconds", "histogram",
               "TTFT of admissions whose prefix-cache lookup hit "
               "(>= one chunk of prefill skipped). Only populated while "
               "the prefix cache is enabled.",
               (), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_serving_ttft_miss_seconds", "histogram",
               "TTFT of admissions that prefilled cold (prefix-cache "
               "miss). Only populated while the prefix cache is enabled.",
               (), DEFAULT_LATENCY_BUCKETS),
    # ---- serving fleet: drain / handoff / router (models/router.py)
    MetricSpec("bigdl_serving_drains_total", "counter",
               "Graceful drains entered by a continuous server (SIGTERM "
               "or drain()): admission stops, in-flight slots leave as "
               "handoff cursors."),
    MetricSpec("bigdl_router_requests_total", "counter",
               "Requests accepted by the fleet router (counted once per "
               "request, before any dispatch attempts)."),
    MetricSpec("bigdl_router_retries_total", "counter",
               "Dispatch attempts re-tried against another replica after "
               "a failed or rejected attempt (bounded, with backoff)."),
    MetricSpec("bigdl_router_requeues_total", "counter",
               "Requests re-dispatched WITH a handoff cursor after their "
               "replica died or drained mid-flight (a subset of "
               "retries: the request had been accepted)."),
    MetricSpec("bigdl_handoff_seconds", "histogram",
               "Wall-clock of producing one serialized prefill handoff "
               "partition on a prefill replica (disaggregation's ship "
               "cost, observed by the router).",
               (), DEFAULT_LATENCY_BUCKETS),
    # ---- cross-request KV prefix cache (models/prefix_cache.py)
    MetricSpec("bigdl_prefix_cache_hits", "counter",
               "Admissions whose chunk-aligned token prefix matched a "
               "cached prefill-state snapshot (tail-only prefill)."),
    MetricSpec("bigdl_prefix_cache_misses", "counter",
               "Admissions that found no cached chunk-aligned prefix and "
               "prefilled from token 0."),
    MetricSpec("bigdl_prefix_cache_evictions", "counter",
               "Prefix-state snapshots dropped LRU-first from the "
               "size-bounded trie, counted one entry at a time (never "
               "clear-at-cap)."),
    MetricSpec("bigdl_prefix_cache_bytes", "gauge",
               "Bytes of prefill-state snapshots currently held by the "
               "serving prefix trie(s) (target + draft in speculative "
               "mode)."),
    # ---- speculative serving (models/serving.py draft=...)
    MetricSpec("bigdl_spec_proposed_tokens_total", "counter",
               "Draft tokens proposed by speculative serving rounds "
               "(spec_len per live slot per round)."),
    MetricSpec("bigdl_spec_accepted_tokens_total", "counter",
               "Draft proposals accepted by target verification (the "
               "per-round bonus token is not counted, so accept rate = "
               "accepted / proposed)."),
    # ---- bucketed batch server (models/lm_server.py)
    MetricSpec("bigdl_lmserver_batch_size", "histogram",
               "Requests per dispatched batch (pre-padding).",
               (), BATCH_SIZE_BUCKETS),
    MetricSpec("bigdl_lmserver_batch_wait_seconds", "histogram",
               "Anchor request's wait from submit to batch dispatch.",
               (), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_lmserver_batches_total", "counter",
               "Batches decoded by the bucketed server."),
    MetricSpec("bigdl_lmserver_requests_total", "counter",
               "Requests served by the bucketed server."),
    MetricSpec("bigdl_lmserver_queue_depth", "gauge",
               "Requests queued or held awaiting same-length company."),
    # ---- training loops (optim/optimizer.py, parallel/distri_optimizer.py)
    MetricSpec("bigdl_train_step_seconds", "histogram",
               "Per-iteration device step time (from one loss fetch's "
               "completion to the next).",
               ("mode",), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_train_data_wait_seconds", "histogram",
               "Host wait on the data pipeline per iteration.",
               ("mode",), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_train_dispatch_seconds", "histogram",
               "Host time handing a step to the device (H2D + enqueue; "
               "async — excludes device compute).",
               ("mode",), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_train_sync_seconds", "histogram",
               "Host block fetching the pipelined losses (device->host "
               "sync point).", ("mode",), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_train_steps_total", "counter",
               "Optimizer iterations completed.", ("mode",)),
    MetricSpec("bigdl_train_records_total", "counter",
               "Training records consumed.", ("mode",)),
    MetricSpec("bigdl_train_records_per_second", "gauge",
               "Most recent per-iteration throughput (records or tokens "
               "per second).", ("mode",)),
    MetricSpec("bigdl_train_validation_seconds", "histogram",
               "Wall-clock of in-training validation passes.",
               ("mode",), DEFAULT_LATENCY_BUCKETS),
    # ---- staged ingest engine (dataset/ingest/)
    MetricSpec("bigdl_ingest_queue_depth", "gauge",
               "Items waiting between ingest stages (stage = shards "
               "done-but-unordered, chunks awaiting decode, batches "
               "done-but-unordered, out = device-ready hand-off queue).",
               ("stage",)),
    MetricSpec("bigdl_ingest_stage_seconds", "histogram",
               "Wall-clock of one ingest work unit (stage = read one "
               "shard / decode one chunk / device_put one batch).",
               ("stage",), DEFAULT_LATENCY_BUCKETS),
    MetricSpec("bigdl_ingest_records_total", "counter",
               "Records handed to the consumer by the ingest engine."),
    MetricSpec("bigdl_ingest_bytes_total", "counter",
               "Raw shard payload bytes read by the reader pool."),
    MetricSpec("bigdl_ingest_batches_total", "counter",
               "Batches handed to the consumer by the ingest engine."),
    MetricSpec("bigdl_ingest_stall_seconds_total", "counter",
               "Starvation attribution: time a stage waited for INPUT "
               "while the pipeline had admission room (waits under "
               "downstream backpressure are charged to nobody). "
               "stage=step is the consumer starving (ingest-bound "
               "training); stage=materialize is DeviceCachedDataSet's "
               "blocking first-fill.", ("stage",)),
    # ---- batch evaluation (optim/evaluator.py)
    MetricSpec("bigdl_eval_batches_total", "counter",
               "Evaluation batches scored."),
    MetricSpec("bigdl_eval_records_total", "counter",
               "Evaluation records scored."),
    MetricSpec("bigdl_eval_batch_seconds", "histogram",
               "Host wall-clock per evaluation batch (async dispatch in "
               "the device-accumulation steady state).",
               (), DEFAULT_LATENCY_BUCKETS),
    # ---- resilience (bigdl_tpu/resilience/, docs/RESILIENCE.md)
    MetricSpec("bigdl_resilience_preemptions_total", "counter",
               "Preemption notices received (SIGTERM/SIGINT or a "
               "cooperative chaos/test trigger)."),
    MetricSpec("bigdl_resilience_snapshot_seconds", "histogram",
               "Wall-clock of the end-of-step preemption snapshot "
               "(model + state + RESUME marker).",
               (), DEFAULT_LATENCY_BUCKETS + (30.0, 120.0)),
    MetricSpec("bigdl_resilience_resumes_total", "counter",
               "Training restarts from a discovered snapshot; "
               "elastic=true when the process/device count changed "
               "(unknown = markerless legacy snapshot).", ("elastic",)),
    # ---- kernel dispatch (ops/int8_matmul.py, parallel/expert.py)
    MetricSpec("bigdl_moe_dispatch_total", "counter",
               "MoE forwards by dispatch path (path label: sort / held). "
               "Counted once per eager call / once per TRACE under jit — "
               "the branch runs at trace time, so this records which "
               "path each compiled MoE program uses, not per-step "
               "traffic. 'sort' (the default) is the capacity path: one "
               "stable argsort of the picks plus gathers, tokens over "
               "an expert's capacity dropped; 'held' is the dropless "
               "layer over the share of the experts that lives on this "
               "chip.", ("path",)),
    MetricSpec("bigdl_ssd_scan_total", "counter",
               "Mamba-2 state-space scans by form (form label: kernel, "
               "the Mosaic kernels of ops/ssd_scan.py, taken on a TPU at "
               "shapes they tile; chunked, the XLA einsums, everywhere "
               "else). Counted once per eager call / once per TRACE under "
               "jit, as bigdl_moe_dispatch_total: which form each compiled "
               "program holds, not per-step traffic.", ("form",)),
    MetricSpec("bigdl_mamba_local_total", "counter",
               "Mamba-2 mixers (nn.Mamba2) by the form their local part "
               "took, everything between the two projections but the scan: "
               "the convolution with its bias, SiLU and split, the D skip, "
               "the gate and the group norm (form label: kernel, the four "
               "Mosaic calls of ops/mamba_local.py, mamba_local_conv / "
               "_gate and their *_bwd, taken on a TPU for bf16 operands "
               "where G * N is whole lane tiles that divide d_inner, a norm "
               "group is whole lane tiles and 128 divides the length; xla, "
               "the jax.numpy lines of nn/mamba.py, everywhere else, a CPU "
               "and inner widths under a lane tile included). Counted once "
               "per eager call / once per TRACE under jit, as "
               "bigdl_ssd_scan_total.", ("form",)),
    MetricSpec("bigdl_short_conv_total", "counter",
               "Double-gated short convolutions (nn.ShortConv) by the form "
               "their local part took: the split, both gates and the "
               "causal depthwise convolution (form label: xla, shifted "
               "multiply-adds that XLA fuses; the only one there is). "
               "Counted once per eager call / once per TRACE under jit, as "
               "bigdl_ssd_scan_total.", ("form",)),
    MetricSpec("bigdl_gated_delta_net_total", "counter",
               "Gated delta-rule linear-attention mixers (nn.GatedDeltaNet) "
               "run: each one fused in-projection, a causal convolution "
               "and SiLU on q, k and v, the recurrence of "
               "ops/delta_rule.py and a gated norm. Counted once per eager "
               "call / once per TRACE under jit, as bigdl_ssd_scan_total."),
    MetricSpec("bigdl_delta_rule_total", "counter",
               "Gated delta-rule recurrences (ops/delta_rule.py) by form "
               "(form label: kernel, the Mosaic calls delta_rule_fwd / "
               "delta_rule_bwd that carry the state in VMEM, on a TPU at "
               "the shapes ops.delta_rule.takes_kernel admits; chunked, "
               "the WY form a chunk at a time as XLA einsums with a scan "
               "over the chunk states, everywhere else and while a "
               "control has replaced _wy). Counted once per eager call / "
               "once per TRACE under jit, as bigdl_ssd_scan_total.",
               ("form",)),
    MetricSpec("bigdl_delta_local_total", "counter",
               "Gated delta-rule mixers (nn.GatedDeltaNet) by the form "
               "their local part took, everything between the two "
               "projections but the recurrence: the convolution with its "
               "SiLU, the heads' L2 norms and q's scale, and the gated "
               "RMSNorm (form label: kernel, the four Mosaic calls of "
               "ops/delta_local.py, delta_local_conv / _gate and their "
               "*_bwd, taken on a TPU for bf16 operands where the "
               "convolution's columns are whole lane tiles, q and k "
               "together and the value width end on a half tile, at most "
               "64 heads and 128 divides the length; xla, the jax.numpy "
               "lines of nn/gated_delta_net.py, everywhere else, a CPU and "
               "tier-1's heads of 8 / 16 included). Counted once per eager "
               "call / once per TRACE under jit, as "
               "bigdl_mamba_local_total.", ("form",)),
    MetricSpec("bigdl_moe_grouped_total", "counter",
               "Grouped products of held expert layers (MoE(dispatch="
               "'held')) by form (form label: kernel, the Mosaic kernels of "
               "ops/grouped_matmul.py over the sorted rows, taken on a TPU "
               "for experts without biases whose widths are whole 128-lane "
               "tiles; xla, the loops over row blocks of "
               "parallel/expert.py, everywhere else). Counted once per "
               "eager call / once per TRACE under jit, as "
               "bigdl_ssd_scan_total: which form each compiled expert "
               "block holds.", ("form",)),
    MetricSpec("bigdl_moe_router_total", "counter",
               "Held expert layers (MoE(dispatch='held')) by their router "
               "(score label: sigmoid, the picked sigmoid scores over "
               "their sum; softmax_picked, a softmax over the picked "
               "logits. input label: own, the router reads the experts' "
               "input; given, it reads a stream the model hands it from "
               "ahead of the layer's attention). Counted once per eager "
               "call / once per TRACE under jit, as bigdl_ssd_scan_total.",
               ("score", "input")),
    MetricSpec("bigdl_lm_head_ce_total", "counter",
               "Fused LM-head cross-entropies by form (form label: "
               "one_pass, the loss and its three gradients from one scan "
               "over row tiles, traced under grad; weighted_one_pass, the "
               "same with a weight a row, fused_lm_head_ce(row_weight=): "
               "the gradients formed with the weights while a tile is "
               "live and the rows' losses kept, which are the weights' "
               "gradient; forward_only, the loss alone, not "
               "differentiated, weighted or not). Counted once per eager "
               "call / once per TRACE under jit, as bigdl_ssd_scan_total.",
               ("form",)),
    MetricSpec("bigdl_decoder_passes_total", "counter",
               "Looped pattern decoders (nn.HybridDecoder(passes=P), "
               "pass_streams: ONE traced body under lax.scan, so a "
               "compiled program holds one copy of the stack and of its "
               "kernel calls). A decoder of one pass that is asked for "
               "its output counts nothing. Counted once per eager call / "
               "once per TRACE under jit, as bigdl_ssd_scan_total."),
    MetricSpec("bigdl_flash_attention_total", "counter",
               "Flash-attention calls by form (form label: band, the "
               "kernels told of a sliding window, named flash_band_*; "
               "mla, a value head that differs from the query/key head, "
               "named flash_mla_*; full, neither: no window or one that "
               "reaches past the first key). "
               "Counted once per eager call / once per TRACE under jit, "
               "as bigdl_ssd_scan_total.", ("form",)),
    MetricSpec("bigdl_flash_band_edges_total", "counter",
               "Banded flash-attention calls (form=band of "
               "bigdl_flash_attention_total) by how the band's two edges "
               "run (edges label: strips, a square unpadded call whose "
               "window is a whole number of its tiles: each edge tile as "
               "two half-height strips against the keys it can see and "
               "the tiles between unmasked; masked, every other banded "
               "call: one loop that masks every tile it meets). Decided "
               "from the call's shapes and the forward's tile, which the "
               "backward's default follows. Counted once per eager call / "
               "once per TRACE under jit, as bigdl_ssd_scan_total.",
               ("edges",)),
    MetricSpec("bigdl_latent_attention_total", "counter",
               "Latent-attention (nn.LatentAttention) forwards by path "
               "(path label: expanded, the latent up-projected to "
               "per-head keys and values and attended as such, the "
               "training path; the only one there is). Counted once per "
               "eager call / once per TRACE under jit, as "
               "bigdl_ssd_scan_total.", ("path",)),
    MetricSpec("bigdl_mtp_modules_total", "counter",
               "Multi-token-prediction modules (nn.MTPModule) run in "
               "training, where each hands the criterion a second stream. "
               "Counted once per eager call / once per TRACE under jit, "
               "as bigdl_ssd_scan_total."),
    MetricSpec("bigdl_remat_kept_total", "counter",
               "Values tagged for block remat to keep (ops/remat.keep), "
               "by the name on its save-list (name label: one of "
               "ops/remat.BLOCK_SAVED_NAMES). Counted once per eager call / "
               "once per TRACE under jit, as bigdl_ssd_scan_total: the "
               "tags a compiled program met, inside a checkpointed block "
               "or not.", ("name",)),
    MetricSpec("bigdl_int8_fallbacks_total", "counter",
               "int8_matmul decode-shaped calls that LOST the fused "
               "kernel because K is off the 128-lane quantum (XLA "
               "dequant fallback at ~2x the int8 byte floor). Any output "
               "dim takes the kernel since the round-10 full-coverage "
               "tiling (the ceil grid masks the partial final tile), so "
               "this stays 0 on real model shapes — V=32000 and "
               "V=151936 included. Counted once per eager call / once "
               "per TRACE under jit (the decision runs at trace time), "
               "and warned once per shape."),
    # ---- compile flight recorder (telemetry/profiling.py tracked_jit)
    MetricSpec("bigdl_compiles_total", "counter",
               "XLA program compilations recorded by tracked_jit — one "
               "per new (site, abstract arg signature).", ("site",)),
    MetricSpec("bigdl_compile_seconds", "histogram",
               "Wall-clock of one tracked_jit trace+lower+compile.",
               ("site",), DEFAULT_LATENCY_BUCKETS + (60.0, 120.0)),
    MetricSpec("bigdl_program_flops", "gauge",
               "cost_analysis FLOPs of the site's most recently compiled "
               "program (per execution of that program).", ("site",)),
    MetricSpec("bigdl_program_bytes_accessed", "gauge",
               "cost_analysis HBM bytes accessed per execution of the "
               "site's most recently compiled program.", ("site",)),
    MetricSpec("bigdl_program_temp_bytes", "gauge",
               "memory_analysis temp (scratch) allocation of the site's "
               "most recently compiled program.", ("site",)),
    MetricSpec("bigdl_program_output_bytes", "gauge",
               "memory_analysis output allocation of the site's most "
               "recently compiled program.", ("site",)),
    MetricSpec("bigdl_compile_cache_evictions_total", "counter",
               "Compiled programs dropped oldest-first from a bounded "
               "program cache (tracked_jit executables, the serving "
               "prefill family, generate() signature family).", ("site",)),
    MetricSpec("bigdl_train_mfu", "gauge",
               "Live model-FLOPs utilization of the training loop: "
               "cost-analysis FLOPs per dispatch / dispatch wall seconds "
               "/ peak chip FLOP/s (absent when the backend reports no "
               "cost analysis, or off-TPU, or for a device kind the "
               "peak table does not list).", ("mode",)),
    MetricSpec("bigdl_device_memory_bytes", "gauge",
               "Device 0 bytes currently allocated (sampled at step "
               "boundaries and slot admission; absent on runtimes "
               "without allocator stats, e.g. CPU)."),
    MetricSpec("bigdl_device_memory_peak_bytes", "gauge",
               "Device 0 peak-bytes-in-use watermark (same sampling "
               "points as bigdl_device_memory_bytes)."),
    # ---- legacy bridge (optim/metrics.py)
    MetricSpec("bigdl_legacy_metric", "gauge",
               "Legacy optim.Metrics counters bridged onto the registry "
               "(scope = one Metrics instance, name = reference counter "
               "name).", ("scope", "name")),
]

#: Span inventory (tracing.span names) with where they fire.
SPAN_SPECS: List[Tuple[str, str]] = [
    ("serving.request", "Async lifecycle of ONE continuous-serving "
     "request (Chrome async events sharing the request id): begins at "
     "submit, instants at admission, ends at completion/failure — with "
     "serving.queue_wait/prefill/insert carrying the same rid arg, a "
     "single dump reconstructs the whole journey."),
    ("serving.queue_wait", "Retrodicted span from a request's submit to "
     "the start of its admission (queue-wait attribution; rid arg links "
     "it to its serving.request lifecycle)."),
    ("serving.prefill", "Out-of-band b=1 prompt prefill + admission "
     "sampling (models/serving.py _admit)."),
    ("serving.insert", "Jitted cache scatter of a prefilled request into "
     "a free slot row."),
    ("serving.decode_block", "One jitted decode_block-token step over all "
     "slots."),
    ("lmserver.request", "Async lifecycle of one bucketed-server request "
     "(submit -> batch dispatch -> completion) under the request id."),
    ("lmserver.gather", "Batcher wait assembling one same-length batch."),
    ("lmserver.decode_batch", "One batched prefill+decode program "
     "(models/lm_server.py)."),
    ("ingest.read_shard", "Reader-pool thread reading + CRC-verifying one "
     "shard (and applying its seeded record shuffle) "
     "(dataset/ingest/engine.py)."),
    ("ingest.decode", "Decode-pool thread running one record chunk "
     "through its cloned decode/collate chain."),
    ("ingest.device_put", "Device-feed thread issuing the async H2D "
     "transfer of one batch (overlaps the step consuming the previous "
     "one)."),
    ("ingest.step", "Consumer-side work between batch pops in "
     "apps/ingest_bench's pipelined measurement (the lane the "
     "read/decode/device_put spans overlap with)."),
    ("ingest.materialize", "DeviceCachedDataSet building its whole-epoch "
     "device cache on first use; the same wall time lands in "
     "bigdl_ingest_stall_seconds_total{stage=materialize} "
     "(dataset/device_cache.py)."),
    ("train.iteration", "One pass of the training loop, a "
     "jax.profiler.StepTraceAnnotation (step_num = neval, k = 1); its "
     "children below partition it. The pass that finds the epoch's "
     "iterator exhausted has k=0 and no dispatch."),
    ("train.data", "Fetching the iteration's batch from the data iterator "
     "(for the device cache: its gather and index programs)."),
    ("train.dispatch", "Handing one training step to the device (H2D + "
     "enqueue)."),
    ("train.sync", "Blocking fetch of the pipelined loss (neval = the "
     "dispatch it waits for, one iteration back)."),
    ("train.log", "Host work after the loss fetch: metrics, MFU gauge, "
     "memory sample, the per-iteration log line, summaries."),
    ("train.hooks", "Validation, checkpoint and summary triggers at an "
     "iteration or epoch boundary."),
    ("train.epoch_end", "From the drain of the epoch's last iteration to the "
     "first train.iteration of the next epoch: epoch log, hooks, shuffle, "
     "iterator rebuild."),
    ("train.validate", "In-training validation pass."),
    ("resilience.snapshot", "End-of-step preemption snapshot: model + "
     "state + RESUME marker (optim/optimizer.py)."),
    ("eval.batches", "One evaluate_batches call (all batches + the final "
     "device->host merge)."),
    ("profiling.compile", "One tracked_jit compilation of a new "
     "(site, signature) — trace+lower+compile wall time "
     "(telemetry/profiling.py)."),
]


class ScopeSpec(NamedTuple):
    """One layer of the step's partition (``telemetry/step_partition.py``):
    a ``jax.named_scope`` the program enters where the work is written, and
    the names that stand for it where no scope is entered."""
    name: str                       # the layer: a row of the table
    entered: str                    # where the scope is entered
    holds: str                      # the work under it
    classes: Tuple[str, ...] = ()   # modules whose own scope (their class
    #                                 name, ``Module.forward``) stands for it
    kernels: Tuple[str, ...] = ()   # Mosaic kernel-name prefixes, the same
    group: bool = False             # holds other layers: a class or kernel
    #                                 under it still names its own layer
    update: bool = False            # the step's stages after the gradient:
    #                                 the pass ``update``


#: The closed vocabulary of step layers. An instruction belongs to the
#: INNERMOST of these in its ``op_name`` (an entered scope keeps the module
#: classes under it, unless it is a ``group``), so a layer's time is its
#: scope less the scopes inside it. ``tests/test_telemetry.py`` holds every
#: ``named_scope`` / ``under_scope`` literal under ``bigdl_tpu/`` to this
#: list and the list to the code.
SCOPE_SPECS: List[ScopeSpec] = [
    ScopeSpec("attn_proj", "nn/attention.py MultiHeadAttention",
              "Everything of the mixer but the core: the q/k/v and out "
              "products and biases, q/k norm, rotation, the output gate, "
              "the repeat of grouped k/v heads."),
    ScopeSpec("attn_core", "nn/attention.py MultiHeadAttention, "
              "LatentAttention; ops/flash_attention.py (the backward rule)",
              "The attention core as the module calls it: the flash "
              "kernels (a forward call and ONE backward call, *_bwd_dkv, "
              "which returns dQ, dK and dV) with the (B,S,N,D) <-> "
              "(B*N,S,D) layout changes inside their forward and backward, "
              "or the XLA cores below use_flash; in decode mode the cache "
              "write too.",
              kernels=("flash_",)),
    ScopeSpec("mla_proj", "nn/attention.py LatentAttention",
              "Latent attention but its core: the two down-projections, "
              "the latents' norms, the two up-projections, rotation, the "
              "assembly of q and k, the out-projection."),
    ScopeSpec("mamba_proj", "nn/mamba.py Mamba2",
              "The in- and out-projection products."),
    ScopeSpec("mamba_local", "nn/mamba.py Mamba2",
              "What is neither a projection nor the scan: the splits of "
              "the in-projection's output, the causal convolution, "
              "softplus / dt, the D skip, the gate, the group norm."),
    ScopeSpec("ssd_scan", "ops/ssd_scan.py ssd_scan (and its backward "
              "rule)", "The Mamba-2 state-space scan, kernel or chunked "
              "form, with the carry between chunks.", kernels=("ssd_",)),
    ScopeSpec("short_conv_proj", "nn/short_conv.py ShortConv",
              "The in- and out-projection products."),
    ScopeSpec("short_conv_local", "nn/short_conv.py ShortConv",
              "What is no projection: the split of the in-projection's "
              "output, the two gates and the causal depthwise convolution "
              "between them."),
    ScopeSpec("delta_proj", "nn/gated_delta_net.py GatedDeltaNet",
              "The fused in-projection (q, k, v, the output gate, beta and "
              "the decay's input: six products as one) and the "
              "out-projection."),
    ScopeSpec("delta_local", "nn/gated_delta_net.py GatedDeltaNet (and the "
              "backward rules of ops/delta_local.py)",
              "What is neither a projection nor the recurrence: the "
              "causal convolution and SiLU on q, k and v, their two L2 "
              "norms, beta and the log-decay, the gated RMSNorm of the "
              "recurrence's output; as the Mosaic calls delta_local_conv "
              "/ delta_local_gate and their *_bwd of ops/delta_local.py "
              "where its path rule says so, with beta, the log-decay and "
              "the sum of proj's cotangent XLA's beside them."),
    ScopeSpec("delta_rule", "ops/delta_rule.py gated_delta_rule",
              "The gated delta rule's chunked recurrence, all of it, both "
              "passes: the chunk's triangular system, the carry over "
              "chunk states, the read-outs."),
    ScopeSpec("mlp", "nn/hybrid.py GatedMLP; nn/attention.py "
              "TransformerEncoderLayer._ffn",
              "A dense feed-forward: its two or three products and the "
              "activation."),
    ScopeSpec("moe_route", "parallel/expert.py MoE (held dispatch)",
              "Router product, top-k, the sort and count of the local "
              "picks, their gathers."),
    ScopeSpec("moe_route_ahead", "parallel/expert.py MoE (held dispatch, "
              "router_input='given')",
              "The routing of a layer whose router reads the stream from "
              "ahead of its attention: router product, top-k, the softmax "
              "or renormalisation, the sort and count of the local picks, "
              "their gathers. It waits on nothing the attention computes."),
    ScopeSpec("moe_experts", "parallel/expert.py MoE (held dispatch, and "
              "the backward rules of both forms); ops/grouped_matmul.py",
              "The grouped product over the held experts' rows, XLA loop "
              "or Mosaic kernels; by the class name, what `MoE` does "
              "outside its three scopes (the token reshape in and out; "
              "the whole of a capacity dispatch, which enters none).",
              classes=("MoE",), kernels=("moe_gmm_",)),
    ScopeSpec("moe_shared", "parallel/expert.py MoE (held dispatch)",
              "The shared expert."),
    ScopeSpec("mtp", "nn/hybrid.py MTPModule; nn/criterion.py "
              "FusedLMHeadCriterion (the second loss)",
              "The multi-token-prediction module; its own row is what no "
              "layer inside it names (the 2E -> E projection, the "
              "shifts).", group=True),
    ScopeSpec("loop_exit", "models/hybrid.py _LoopedLM; nn/criterion.py "
              "FusedLMHeadCriterion._exit_loss",
              "A looped decoder's exit gate: the gate's product with each "
              "pass's stream, the log-sigmoids, the exit distribution "
              "over the passes, its entropy and the loss's last sums, "
              "forward and backward (plain jax.numpy: autodiff carries "
              "the scope to the transpose). The passes' cross-entropies "
              "are lm_head_ce's."),
    ScopeSpec("norm", "class scope", "A norm between blocks (a norm inside "
              "attn_proj, mla_proj or mamba_local belongs to that layer).",
              classes=("RMSNorm", "LayerNorm")),
    ScopeSpec("embed", "class scope", "The embedding lookup and its "
              "scatter-add backward.", classes=("LookupTable",)),
    ScopeSpec("linear", "class scope", "A Linear outside any layer above "
              "(a convnet's classifier, a head module).",
              classes=("Linear", "LMHead", "TiedLMHead"),
              kernels=("int8_matmul",)),
    ScopeSpec("conv", "class scope", "Convolutions.",
              classes=("SpatialConvolution", "SpatialShareConvolution",
                       "SpaceToDepthConv7", "SpatialDilatedConvolution",
                       "SpatialFullConvolution", "VolumetricConvolution")),
    ScopeSpec("batchnorm", "class scope (ops/batch_norm.py runs under "
              "it)", "Batch normalisation: statistics, normalise, its "
              "custom backward.",
              classes=("BatchNormalization", "SpatialBatchNormalization",
                       "VolumetricBatchNormalization")),
    ScopeSpec("pool", "class scope", "Pooling.",
              classes=("SpatialMaxPooling", "SpatialAveragePooling",
                       "VolumetricMaxPooling")),
    ScopeSpec("activation", "class scope", "Activation modules and the "
              "residual add of a convnet block.",
              classes=("ReLU", "ReLU6", "PReLU", "LeakyReLU", "ELU",
                       "Sigmoid", "Tanh", "SoftMax", "LogSoftMax",
                       "SoftPlus", "HardTanh", "CAddTable", "Dropout")),
    ScopeSpec("param_cast", "optim/optimizer.py make_training_loss_fn",
              "The compute-dtype view of the master parameters, and the "
              "gradient's way back through it.", update=True),
    ScopeSpec("criterion", "optim/optimizer.py make_training_loss_fn",
              "criterion.apply and the regulariser; its own row is what "
              "lm_head_ce does not hold.", group=True),
    ScopeSpec("lm_head_ce", "ops/lm_head_ce.py (the primal and both "
              "rules)", "The fused LM-head cross-entropy: its loops over "
              "row tiles."),
    ScopeSpec("grad_sync", "parallel/distri_optimizer.py",
              "The collective a step builder writes itself: ZeRO-1's "
              "reduce-scatter and means, the bf16 payload cast, FSDP's "
              "sharding constraint (GSPMD's own all-reduce carries the "
              "metadata of the gradient it reduces).", update=True),
    ScopeSpec("grad_clip", "optim/optimizer.py clipped_update",
              "The clamp and the global-L2 rescale.", update=True),
    ScopeSpec("optim_update", "optim/optimizer.py clipped_update; "
              "parallel/distri_optimizer.py (ZeRO-1's slice and gather)",
              "optim.update: the optimiser's arithmetic on parameters and "
              "state.", update=True),
]


class _Instruments:
    """Attribute-addressed families for one registry: ``ins.<name>`` with
    the ``bigdl_`` prefix stripped. Built once per (registry) and cached
    on the registry object — instrument sites pay one dict lookup."""

    def __init__(self, registry: MetricsRegistry):
        for spec in METRIC_SPECS:
            fam = registry.from_spec(spec)
            if not spec.labels:
                fam.labels()  # expose at 0 before first use (scrape-friendly)
            setattr(self, spec.name[len("bigdl_"):], fam)


def instruments(registry: MetricsRegistry) -> _Instruments:
    """Get-or-build the catalogue's families on ``registry``."""
    ins = getattr(registry, "_bigdl_instruments", None)
    if ins is None:
        ins = _Instruments(registry)
        registry._bigdl_instruments = ins
    return ins
