"""App-main tests (reference strategy §4.5: ``SparkModeSpec.scala:24-42``
literally invokes the example ``Train.main``s — same idea, minus the cluster)."""

import json
import os

import numpy as np
import pytest

from bigdl_tpu.apps import (autoencoder, lenet, perf, resnet, rnn,
                            textclassifier, vgg)


class TestTrainMains:
    def test_lenet_train_then_test(self, tmp_path, capsys):
        ck = str(tmp_path / "ck")
        lenet.train(["-b", "64", "-e", "1", "--synthetic-size", "256",
                     "--checkpoint", ck, "--summary", str(tmp_path / "tb")])
        assert os.path.exists(os.path.join(ck, "model_final"))
        # checkpoint + state snapshots written by the trigger
        assert any(f.startswith("model.") for f in os.listdir(ck))
        lenet.test(["--model", f"{ck}/model_final",
                    "--synthetic-size", "128", "-b", "64"])
        assert "Top1Accuracy" in capsys.readouterr().out

    def test_lenet_resume_flags(self, tmp_path):
        ck = str(tmp_path / "ck")
        lenet.train(["-b", "64", "-e", "1", "--synthetic-size", "128",
                     "--checkpoint", ck, "--overWriteCheckpoint"])
        lenet.train(["-b", "64", "-e", "2", "--synthetic-size", "128",
                     "--model", f"{ck}/model", "--state", f"{ck}/state"])

    def test_rnn_train(self):
        rnn.train(["-b", "8", "-e", "1", "--synthetic-size", "64",
                   "--hiddenSize", "16", "--sequenceLength", "12"])

    def test_autoencoder_train(self):
        autoencoder.train(["-b", "32", "-e", "1", "--synthetic-size", "64"])

    def test_textclassifier_train(self, tmp_path):
        ck = str(tmp_path / "ck")
        textclassifier.train(["-b", "16", "-e", "1", "--synthetic-size", "64",
                              "--maxSequenceLength", "150",
                              "--embeddingDim", "20", "--checkpoint", ck])
        assert os.path.exists(os.path.join(ck, "model_final"))
        assert os.path.exists(os.path.join(ck, "classifier_bundle"))

    def test_udfpredictor_over_bundle(self, tmp_path, capsys):
        from bigdl_tpu.apps import udfpredictor
        ck = str(tmp_path / "ck")
        textclassifier.train(["-b", "16", "-e", "2", "--synthetic-size", "64",
                              "--maxSequenceLength", "150",
                              "--embeddingDim", "16", "--checkpoint", ck])
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "one.txt").write_text("klassam klassan klassao " * 30)
        (docs / "two.txt").write_text("klassbm klassbn klassbo " * 30)
        rows = udfpredictor.run(["--modelPath", f"{ck}/classifier_bundle",
                                 "-f", str(docs), "-b", "4"])
        assert len(rows) == 2
        out = capsys.readouterr().out
        assert "one.txt" in out and "two.txt" in out
        # the plain-callable UDF form works too
        from bigdl_tpu.utils import file_io
        udf = udfpredictor.make_udf(file_io.load(f"{ck}/classifier_bundle"))
        assert udf("klassam klassan") in (1, 2, 3, 4)

    def test_seqfilegen_round_trip(self, tmp_path, capsys):
        from bigdl_tpu.apps import seqfilegen
        from bigdl_tpu.dataset.shards import list_shards, read_shard
        from PIL import Image
        base = tmp_path / "imgs"
        for cls in ["cat", "dog"]:
            d = base / "train" / cls
            d.mkdir(parents=True)
            for i in range(5):
                Image.new("RGB", (8, 8), (i * 20, 0, 0)).save(d / f"{i}.png")
        out = str(tmp_path / "shards")
        seqfilegen.main(["-f", str(base), "-o", out, "-p", "2", "-b", "3"])
        assert "packed 10 records" in capsys.readouterr().out
        records = [r for s in list_shards(os.path.join(out, "train"))
                   for r in read_shard(s)]
        assert len(records) == 10
        assert sorted({r.label for r in records}) == [1.0, 2.0]

    def test_inception_shard_pipeline(self, tmp_path):
        # pack a tiny PNG tree, then drive the ImageNet2012-style shard
        # pipeline: MT decode -> crop -> normalize -> batch -> prefetch
        from bigdl_tpu.apps import seqfilegen
        from bigdl_tpu.apps.inception import _shard_dataset
        from PIL import Image
        base = tmp_path / "imgs"
        for ci, cls in enumerate(["cat", "dog"]):
            d = base / "train" / cls
            d.mkdir(parents=True)
            for i in range(4):
                Image.new("RGB", (16, 12), (ci * 100, i * 30, 5)).save(
                    d / f"{i}.png")
        out = str(tmp_path / "shards")
        seqfilegen.main(["-f", str(base), "-o", out, "-b", "8"])
        for train in (True, False):
            ds = _shard_dataset(os.path.join(out, "train"), batch=4,
                                train=train)
            batches = list(ds.data(train=False))
            assert len(batches) == 2
            assert batches[0].data.shape == (4, 224, 224, 3)
            assert set(np.asarray(batches[0].labels)) <= {1.0, 2.0}

    def test_imageclassifier_predicts(self, tmp_path, capsys, monkeypatch):
        from bigdl_tpu.apps import imageclassifier, modelvalidator
        from bigdl_tpu.utils import file_io
        from test_modelvalidator import _tiny_builder, _write_folder
        monkeypatch.setitem(modelvalidator._MODELS,
                            "tiny", (_tiny_builder, 32,
                                     (127.0,) * 3, (64.0,) * 3))
        folder = _write_folder(tmp_path)
        file_io.save(_tiny_builder(2), str(tmp_path / "snap"))
        imageclassifier.main(["-f", folder, "-m", "tiny", "-t", "bigdl",
                              "--modelPath", str(tmp_path / "snap"),
                              "-b", "4"])
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 12 and all("\t" in line for line in out)

    def test_textclassifier_real_folder_layout(self, tmp_path):
        # 20_newsgroup-style tree + tiny GloVe file exercising the real path
        base = tmp_path / "data"
        for cat in ["alt.atheism", "sci.space"]:
            d = base / "20_newsgroup" / cat
            d.mkdir(parents=True)
            for i in range(12):
                word = "god" if cat == "alt.atheism" else "orbit"
                (d / str(i)).write_text(f"the {word} text {word} here " * 30)
        glove = base / "glove.6B"
        glove.mkdir()
        rng = np.random.RandomState(0)
        words = ["the", "god", "orbit", "text", "here"]
        (glove / "glove.6B.20d.txt").write_text("\n".join(
            w + " " + " ".join(f"{v:.4f}" for v in rng.randn(20))
            for w in words))
        textclassifier.train(["--folder", str(base), "-b", "8", "-e", "1",
                              "--maxSequenceLength", "150",
                              "--embeddingDim", "20"])


class TestPerfHarness:
    def test_local_perf_json(self, capsys):
        perf.main(["--model", "lenet5", "-b", "32", "-i", "3",
                   "--precision", "fp32"])
        out = capsys.readouterr().out.strip().splitlines()[-1]
        rec = json.loads(out)
        assert rec["model"] == "lenet5" and rec["iterations"] == 3
        assert rec["records_per_sec_incl_compile"] > 0

    def test_distributed_perf(self, capsys):
        perf.main(["--model", "lenet5", "-b", "64", "-i", "2",
                   "--distributed", "--precision", "fp32"])
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["distributed"] is True and rec["devices"] == 8

    def test_unknown_model_rejected(self):
        with pytest.raises(SystemExit):
            perf.main(["--model", "alexnet9000"])

    @pytest.mark.parametrize("flags", [["--remat", "conv"],
                                       ["--moeDispatch", "einsum"]])
    def test_the_retired_levers_are_refused(self, flags, capsys):
        # a remat policy and an A/B flag that only the pre-benchmark
        # scripts pulled (PR 44): argparse refuses both before any model
        # is built
        with pytest.raises(SystemExit) as exc:
            perf.main(["--model", "lenet5", *flags])
        assert exc.value.code == 2
        assert flags[0] in capsys.readouterr().err

    @pytest.mark.slow  # ~32s: full cp train loop on the 1-core CPU box
    def test_transformer_lm_train_and_context_parallel(self, tmp_path):
        from bigdl_tpu.apps import transformer
        ck = str(tmp_path / "ck")
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                           "--synthetic-size", "32", "--checkpoint", ck])
        from bigdl_tpu.utils import file_io
        assert file_io.load(f"{ck}/model_final") is not None
        # sequence-parallel modes over the 8-device mesh (ulysses requires
        # num_heads divisible by the seq-axis size)
        for mode, heads in (("ring", "4"), ("ulysses", "8")):
            transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                               "--synthetic-size", "16", "--numHeads", heads,
                               "--contextParallel", mode])
        # balanced causal ring layout end-to-end (seqLen % 2P == 0)
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                           "--synthetic-size", "16", "--numHeads", "4",
                           "--contextParallel", "ring",
                           "--ringLayout", "zigzag"])
        # dp=2 x tp=4 with Megatron-SP regions through the Optimizer path
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                           "--synthetic-size", "16", "--numHeads", "4",
                           "--tensorParallel", "4"])
        # MoE FFN variant (top-2 of 4 experts)
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                           "--synthetic-size", "16", "--moeExperts", "4"])

    @pytest.mark.slow  # ~19s: two cp train sessions + resume
    def test_transformer_context_parallel_resume(self, tmp_path):
        """--contextParallel now composes with --model/--state: the cp
        loop writes (model.N, state.N) pairs through the resilience
        coordinator, and a resume continues epoch/neval counters from the
        saved driver instead of raising (ISSUE: transformer.py:150)."""
        from bigdl_tpu.apps import transformer
        from bigdl_tpu.resilience import coordinator
        ck = str(tmp_path / "ck")
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "1",
                           "--synthetic-size", "16", "--numHeads", "4",
                           "--contextParallel", "ring",
                           "--checkpoint", ck])
        point = coordinator.latest_resume_point(ck)
        assert point is not None  # cadence pair + marker written
        assert point.marker["mesh"]["sync_mode"] == "context-parallel"
        # resume for a second epoch from the pair (also covers the
        # cp-format {"embed","tail"} param split restore)
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "2",
                           "--synthetic-size", "16", "--numHeads", "4",
                           "--contextParallel", "ring",
                           "--model", point.model_path,
                           "--state", point.state_path])
        # and --autoResume discovers the pair without explicit paths
        transformer.train(["-b", "8", "--seqLen", "32", "-e", "2",
                           "--synthetic-size", "16", "--numHeads", "4",
                           "--contextParallel", "ring",
                           "--checkpoint", ck, "--autoResume"])

    def test_transformer_text_lm_end_to_end(self, tmp_path, capsys):
        """--textFile: BPE-tokenize real text, train, generate TEXT back."""
        from bigdl_tpu.apps import transformer
        corpus = tmp_path / "corpus.txt"
        corpus.write_text("the quick brown fox jumps over the lazy dog\n"
                          "the quick brown fox is quick and lazy\n" * 4)
        ck = str(tmp_path / "ck")
        transformer.train(["--textFile", str(corpus), "--bpeVocab", "280",
                           "--seqLen", "8", "-b", "4", "-e", "2",
                           "--checkpoint", ck, "--fusedHead"])
        transformer.generate_cmd(["--model", f"{ck}/model_final",
                                  "--tokenizer", f"{ck}/tokenizer.bigdl",
                                  "--prompt", "the quick",
                                  "--maxNewTokens", "4", "--greedy"])
        out = capsys.readouterr().out
        assert "prompt:       'the quick'" in out
        assert "continuation:" in out

    def test_transformer_generate_subcommand(self, tmp_path, capsys):
        from bigdl_tpu.apps import transformer
        ck = str(tmp_path / "ck")
        transformer.train(["-b", "8", "--seqLen", "16", "-e", "1",
                           "--vocab", "32", "--synthetic-size", "16",
                           "--checkpoint", ck])
        transformer.generate_cmd(["--model", f"{ck}/model_final",
                                  "--prompt", "3,5,7",
                                  "--maxNewTokens", "6", "--greedy"])
        out = capsys.readouterr().out
        assert "prompt:       [3, 5, 7]" in out
        assert "continuation:" in out
        # beam + int8 paths through the same CLI
        transformer.generate_cmd(["--model", f"{ck}/model_final",
                                  "--prompt", "3,5,7", "--maxNewTokens", "4",
                                  "--numBeams", "3", "--int8"])
        assert "continuation:" in capsys.readouterr().out

    def test_transformer_generate_from_hf_checkpoint(self, capsys,
                                                     tmp_path):
        # raw-HF-id mode: a checkpoint dir WITHOUT tokenizer files (copy
        # the fixture minus tokenizer.json)
        import os
        import shutil
        from bigdl_tpu.apps import transformer
        res = os.path.join(os.path.dirname(__file__), "resources",
                           "hf_tiny_gpt2")
        bare = tmp_path / "bare"
        bare.mkdir()
        for f in ("config.json", "model.safetensors"):
            shutil.copy(os.path.join(res, f), bare / f)
        transformer.generate_cmd(["--fromHF", str(bare),
                                  "--prompt", "5,17,42",
                                  "--maxNewTokens", "4", "--greedy"])
        out = capsys.readouterr().out
        assert "prompt:       [5, 17, 42]" in out  # HF 0-based round trip
        assert "continuation:" in out

    def test_transformer_rejects_model_and_hf_together(self):
        import pytest
        from bigdl_tpu.apps import transformer
        with pytest.raises(SystemExit, match="not both"):
            transformer.generate_cmd(["--fromHF", "x", "--model", "y"])

    @pytest.mark.slow  # 10.0-13.5s: two perf-harness compiles, one under shard_map
    def test_context_parallel_matches_sequential_loss(self):
        # PE offsets + pmean correctness: first-step loss of the seq-parallel
        # path must equal the plain path on the same weights and batch
        import jax
        import jax.numpy as jnp
        import bigdl_tpu as bt
        from bigdl_tpu import nn as _nn
        from bigdl_tpu.apps.transformer import (_synthetic_corpus,
                                                _train_context_parallel)
        from bigdl_tpu.dataset.base import DataSet, SampleToBatch
        from bigdl_tpu.models import transformer as tmodel
        from bigdl_tpu.nn.module import functional_apply

        bt.utils.manual_seed(6)
        model = tmodel.build_lm(16, 32, 2, 64, num_layers=1, max_len=64,
                                seq_axis="seq")
        crit = _nn.TimeDistributedCriterion(_nn.ClassNLLCriterion())
        samples = _synthetic_corpus(8, 32, 16)
        batch = next(iter((DataSet.array(samples) >> SampleToBatch(8))
                          .data(train=False)))
        tokens, targets = jnp.asarray(batch.data), jnp.asarray(batch.labels)

        # plain (replicated) loss on the same params, seq_axis ignored by
        # building an equivalent unsharded model with the SAME weights
        plain = tmodel.build_lm(16, 32, 2, 64, num_layers=1, max_len=64)
        plain.load_parameter_tree(model.parameter_tree())
        out, _ = functional_apply(plain, plain.parameter_tree(),
                                  plain.buffer_tree(), tokens,
                                  training=False)
        want = float(crit.apply(out, targets))

        # seq-parallel loss via the app's own loop internals
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from bigdl_tpu.parallel.mesh import MeshTopology
        mesh = MeshTopology(sequence=8).build()
        embed = _nn.Sequential().add(model[0]).add(model[1])
        tail = _nn.Sequential().add(model[2]).add(model[3]).add(model[4])

        def tail_loss(p_tail, x_embedded, tgt):
            o, _ = functional_apply(tail, p_tail, {}, x_embedded,
                                    training=False)
            return jax.lax.pmean(
                crit.apply(o, tgt).astype(jnp.float32), "seq")

        sharded = shard_map(tail_loss, mesh=mesh,
                            in_specs=(P(), P(None, "seq", None),
                                      P(None, "seq")),
                            out_specs=P(), check_vma=False)
        x, _ = functional_apply(embed, embed.parameter_tree(), {}, tokens,
                                training=False)
        got = float(sharded(tail.parameter_tree(), x, targets))
        assert abs(got - want) < 1e-3, (got, want)

    def test_transformer_lm_learns_grammar(self):
        # the synthetic corpus is 90% deterministic: a real LM must beat
        # uniform log-loss (log 64 ~= 4.16) by a wide margin
        import jax.numpy as jnp
        from bigdl_tpu.apps.transformer import _synthetic_corpus
        from bigdl_tpu.models import transformer as tmodel
        from bigdl_tpu import nn as _nn
        from bigdl_tpu.dataset.base import DataSet, SampleToBatch
        from bigdl_tpu.optim import Optimizer, Adam, Trigger
        import bigdl_tpu as bt
        bt.utils.manual_seed(3)
        ds = (DataSet.array(_synthetic_corpus(96, 32, 16))
              >> SampleToBatch(16))
        model = tmodel.build_lm(16, 32, 2, 64, num_layers=1, max_len=64)
        crit = _nn.TimeDistributedCriterion(_nn.ClassNLLCriterion())
        opt = (Optimizer(model, ds, crit)
               .set_optim_method(Adam(learningrate=3e-3))
               .set_end_when(Trigger.max_epoch(6)))
        trained = opt.optimize()
        params, buffers = trained.parameter_tree(), trained.buffer_tree()
        from bigdl_tpu.nn.module import functional_apply
        batch = next(iter(ds.data(train=False)))
        out, _ = functional_apply(trained, params, buffers,
                                  jnp.asarray(batch.data), training=False)
        loss = float(crit.apply(out, jnp.asarray(batch.labels)))
        assert loss < 2.0, f"LM failed to learn the grammar: {loss}"

    @pytest.mark.slow  # ~13s: full perf-harness compile; tier-1 wall budget
    def test_transformer_perf_workload(self, capsys):
        perf.main(["--model", "transformer", "-b", "2", "-i", "2",
                   "--warmup", "1", "--precision", "fp32"])
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["model"] == "transformer"
        assert rec["records_per_sec_incl_compile"] > 0

    @pytest.mark.slow  # ~17s: MoE perf-harness compile; tier-1 wall budget
    def test_perf_moe_flag_builds_moe_model(self, capsys):
        perf.main(["--model", "transformer", "-b", "2", "-i", "1",
                   "--warmup", "1", "--precision", "fp32",
                   "--moeExperts", "2"])
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["records_per_sec_incl_compile"] > 0

    @pytest.mark.slow  # ~16s: adamw+remat perf compile; tier-1 wall budget
    def test_perf_adamw_remat_block(self, capsys):
        perf.main(["--model", "transformer", "-b", "2", "-i", "1",
                   "--warmup", "1", "--precision", "fp32",
                   "--optim", "adamw", "--optStateDtype", "bf16",
                   "--remat", "block"])
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["records_per_sec_incl_compile"] > 0


class TestIngestBench:
    """Shard-ingest benchmark app (apps/ingest_bench): generate -> read ->
    decode stages produce sane JSON on a tiny corpus (the on-chip train
    stage and full-size corpus are exercised by the PERF.md runs)."""

    @pytest.mark.slow  # ~10s: three ingest stages; tier-1 wall budget
    def test_generate_read_decode(self, tmp_path, capsys):
        from bigdl_tpu.apps import ingest_bench
        out = str(tmp_path / "shards")
        ingest_bench.main(["generate", "-o", out, "-n", "64",
                           "--perShard", "32"])
        gen = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert gen["records"] == 64
        ingest_bench.main(["read", "-s", out, "--budget", "5"])
        rd = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rd["records_per_sec"] > 0
        ingest_bench.main(["decode", "-s", out, "-b", "8", "-w", "2",
                           "--budget", "5"])
        dec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert dec["records_per_sec"] > 0


class TestFromHFTextServing:
    """--fromHF on a checkpoint dir that carries its tokenizer: prompts
    are TEXT end-to-end (the HFTokenizer auto-load path)."""

    def test_generate_text_prompt_from_hf_dir(self, capsys):
        import os
        from bigdl_tpu.apps import transformer
        res = os.path.join(os.path.dirname(__file__), "resources",
                           "hf_tiny_gpt2")
        transformer.generate_cmd(["--fromHF", res,
                                  "--prompt", "the quick brown",
                                  "--maxNewTokens", "6", "--greedy"])
        out = capsys.readouterr().out
        assert "prompt:       'the quick brown'" in out
        assert "continuation:" in out


class TestFromHFLlamaSentencePiece:
    """Llama-2-style checkpoint dirs (tokenizer.model, no tokenizer.json)
    speak TEXT end-to-end — round 5's sentencepiece reader wired into the
    --fromHF auto-load path."""

    def test_generate_text_prompt_with_spm_tokenizer(self, capsys,
                                                     tmp_path):
        from bigdl_tpu.apps import transformer as app
        from bigdl_tpu.interop.hf import save_hf_checkpoint
        from bigdl_tpu.interop.sentencepiece import (BYTE, CONTROL, NORMAL,
                                                     UNKNOWN, write_model)
        from bigdl_tpu.models import transformer as tlib
        import bigdl_tpu as bt

        bt.utils.manual_seed(5)
        pieces = [("<unk>", 0.0, UNKNOWN), ("<s>", 0.0, CONTROL),
                  ("</s>", 0.0, CONTROL)]
        pieces += [(f"<0x{b:02X}>", -100.0 - b * 1e-3, BYTE)
                   for b in range(256)]
        for i, w in enumerate(["▁the", "▁quick", "▁brown", "▁fox",
                               "the", "quick", "fox", "▁"]):
            pieces.append((w, -1.0 - 0.5 * i, NORMAL))
        vocab = len(pieces)
        model = tlib.build_lm(vocab, embed_dim=32, num_heads=2, ffn_dim=64,
                              num_layers=1, max_len=64, rope=True,
                              activation="swiglu", norm="rms",
                              tie_embeddings=False)
        hf_dir = str(tmp_path / "llama")
        save_hf_checkpoint(model, hf_dir)
        write_model(f"{hf_dir}/tokenizer.model", pieces,
                    model_type="unigram", byte_fallback=True)
        app.generate_cmd(["--fromHF", hf_dir,
                          "--prompt", "the quick brown fox",
                          "--maxNewTokens", "4", "--greedy"])
        out = capsys.readouterr().out
        assert "prompt:       'the quick brown fox'" in out
        assert "continuation:" in out


class TestLlamaBlockContextParallel:
    """--llamaBlock --contextParallel: the long-context rope training
    recipe is CLI-reachable end to end (round 5)."""

    def test_train_ring_rope(self, capsys):
        from bigdl_tpu.apps import transformer
        transformer.train(["-b", "8", "--seqLen", "32", "--maxEpoch", "1",
                           "--llamaBlock", "--contextParallel", "ring",
                           "--ringLayout", "zigzag", "--numLayers", "1",
                           "--embedDim", "16", "--numHeads", "2",
                           "--synthetic-size", "16"])

    def test_llamablock_moe_refused(self):
        from bigdl_tpu.apps import transformer
        with pytest.raises(SystemExit, match="moeExperts"):
            transformer.train(["--llamaBlock", "--moeExperts", "4"])
