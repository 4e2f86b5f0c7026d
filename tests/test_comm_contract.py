"""Communication-pattern contract: the ZeRO-1 sharded step must lower to
reduce-scatter + all-gather (the reference AllReduceParameter's
slice-ownership exchange, ``parameters/AllReduceParameter.scala:62``), NOT a
plain all-reduce — the whole point of the sharded plane is that no device
materializes the full gradient reduction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset.base import DataSet, Sample, SampleToBatch
from bigdl_tpu.models import lenet
from bigdl_tpu.optim import SGD
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.mesh import MeshTopology


def _opt(sync_mode):
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (28, 28, 1)).astype("float32"),
                      float(rng.integers(1, 11))) for _ in range(16)]
    ds = DataSet.array(samples, distributed=True) >> SampleToBatch(16)
    opt = DistriOptimizer(lenet.build(10), ds, nn.ClassNLLCriterion(),
                          topology=MeshTopology(data=8))
    opt.sync_mode = sync_mode
    opt.set_optim_method(SGD(learningrate=0.1))
    return opt


def test_sharded_step_compiles_to_reduce_scatter_all_gather():
    opt = _opt("sharded")
    step = opt._build_step()  # also sets the flat geometry (opt._pad)
    buffers = opt.model.buffer_tree()
    opt_state = opt._init_opt_state(opt.model.parameter_tree())
    _, buffers, opt_state = opt._place_state(opt.model.parameter_tree(),
                                             buffers, opt_state)
    from jax.flatten_util import ravel_pytree
    flat, _ = ravel_pytree(opt.model.parameter_tree())
    flat = jax.device_put(jnp.pad(flat, (0, opt._pad)), opt._replicated)
    # collectives are inserted by SPMD partitioning: inspect COMPILED HLO
    txt = step.jitted.lower(flat, buffers, opt_state, jax.random.key(0),
                            jnp.zeros((16, 28, 28, 1)),
                            jnp.ones((16,))).compile().as_text()
    assert "reduce-scatter" in txt, "ZeRO-1 step lost its reduce-scatter"
    assert "all-gather" in txt, "ZeRO-1 step lost its weight all-gather"


def test_allreduce_step_compiles_to_all_reduce():
    opt = _opt("allreduce")
    step = opt._build_step()
    params = opt.model.parameter_tree()
    buffers = opt.model.buffer_tree()
    opt_state = opt._init_opt_state(params)
    params, buffers, opt_state = opt._place_state(params, buffers, opt_state)
    txt = step.lower(params, buffers, opt_state, jax.random.key(0),
                     jnp.zeros((16, 28, 28, 1)),
                     jnp.ones((16,))).compile().as_text()
    assert "all-reduce" in txt
    assert "reduce-scatter" not in txt  # plain DP: no slice ownership


def test_ring_attention_compiles_to_collective_permute():
    # ring attention's defining trait: K/V blocks ROTATE around the ring
    # (ppermute -> collective-permute), no all-gather of the full sequence
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.nn.module import functional_apply
    enc = nn.TransformerEncoder(1, 16, 2, 32, causal=True, seq_axis="seq")
    mesh = MeshTopology(sequence=8).build()
    params, buffers = enc.parameter_tree(), enc.buffer_tree()
    x = jnp.zeros((2, 32, 16))

    def loss(p, b, xx):
        y, _ = functional_apply(enc, p, b, xx, training=False)
        return jnp.sum(y ** 2)

    fn = jax.jit(shard_map(loss, mesh=mesh,
                           in_specs=(P(), P(), P(None, "seq", None)),
                           out_specs=P(), check_vma=False))
    txt = fn.lower(params, buffers, x).compile().as_text()
    assert "collective-permute" in txt, "ring attention lost its ring"


def test_dp_cp_ring_stays_in_coset_and_grads_all_reduce():
    """dp x cp contract (the long-context pretraining layout): on a
    (data=2, seq=4) mesh the K/V ring must rotate WITHIN each data
    group's seq coset — every collective-permute source/target pair
    stays inside {0..3} or {4..7} — while the replicated-parameter
    gradients still all-reduce ACROSS groups. A regression that flattens
    the ring over all 8 devices would mix sequence shards from
    different batch slices (silent numerics corruption, not a crash)."""
    import re
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.nn.module import functional_apply
    enc = nn.TransformerEncoder(1, 16, 2, 32, causal=True, seq_axis="seq")
    mesh = MeshTopology(data=2, sequence=4).build()
    params, buffers = enc.parameter_tree(), enc.buffer_tree()
    x = jnp.zeros((4, 16, 16))

    def loss(p, b, xx):
        y, _ = functional_apply(enc, p, b, xx, training=False)
        return jnp.sum(y ** 2)

    fn = jax.jit(jax.grad(shard_map(
        loss, mesh=mesh, in_specs=(P(), P(), P("data", "seq", None)),
        out_specs=P(), check_vma=False)))
    txt = fn.lower(params, buffers, x).compile().as_text()
    assert "collective-permute" in txt, "dp x cp lost its seq ring"
    assert "all-reduce" in txt, "dp x cp lost its data gradient sync"
    pair_blobs = re.findall(r"source_target_pairs=\{([^}]+(?:\},\{[^}]+)*)\}",
                            txt)
    assert pair_blobs, "no collective-permute pairs in compiled HLO"
    for blob in pair_blobs:
        for pair in re.findall(r"(\d+),(\d+)", blob):
            s, t = int(pair[0]), int(pair[1])
            assert s // 4 == t // 4, (
                f"ring hop {s}->{t} crosses the data-group boundary: "
                "sequence shards from different batch slices got mixed")


@pytest.mark.parametrize("dispatch", ["sort", "held"])
def test_expert_parallel_step_routes_over_expert_axis(dispatch):
    """EP collective RECORD (round-5 VERDICT #8): expert parallelism is
    GSPMD-sharded (``expert_param_specs`` + jit), so WHICH collective
    implements the token routing is the partitioner's choice — on this
    toolchain it computes each device's experts against all tokens and
    combines with an all-reduce (no all_to_all). The contract this pins:
    some collective must reduce over EXPERT-axis peer groups, not just
    the data axis — on a (data=2, expert=4) mesh the expert cosets are
    {0..3}/{4..7}, distinct from the data-axis pairs {0,4}... A
    replicated-weights regression would sync grads over data only and
    fail here. Pinned for both paths of the layer: the capacity path's
    gathers and the held path's grouped product over sorted rows must
    each leave the expert-coset pattern intact, not trade it for a
    replicate-everything fallback."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bigdl_tpu.nn.module import functional_apply
    from bigdl_tpu.parallel.expert import MoE, expert_param_specs

    mesh = MeshTopology(data=2, expert=4).build()
    moe = MoE(16, 32, n_experts=4, k=2, dispatch=dispatch)
    params = moe.parameter_tree()
    buffers = moe.buffer_tree()
    specs = expert_param_specs(moe)
    p_sh = {k: NamedSharding(mesh, specs.get(k, P())) for k in params}
    params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
    x_sh = NamedSharding(mesh, P("data"))
    x = jax.device_put(jnp.ones((64, 16), jnp.float32), x_sh)

    def loss(p, b, x):
        out, _ = functional_apply(moe, p, b, x, training=False)
        return jnp.sum(out)

    fn = jax.jit(jax.grad(loss), in_shardings=(p_sh, None, x_sh))
    txt = fn.lower(params, buffers, x).compile().as_text()
    # expert cosets {0..3}/{4..7} appear either as the iota-v2 form
    # "[2,4]<=[8]" (2 groups of 4 in device order — what this toolchain
    # emits; the data-axis grad sync is the distinct "[4,2]<=[2,4]T(1,0)")
    # or as explicit brace lists
    iota_form = "replica_groups=[2,4]<=[8]" in txt
    brace_form = re.search(
        r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}", txt) is not None
    assert iota_form or brace_form, \
        "no collective reduces over the expert-axis cosets: " + \
        str(sorted(set(re.findall(r"replica_groups=\S*", txt))))


def test_fsdp_tp_composed_step_collectives():
    """fsdp x tp (first composed dryrun mode, ROADMAP #3): every weight
    shard carries BOTH mesh axes at rest — fsdp_param_specs composes the
    data axis onto a dim the Megatron spec leaves free. Collective RECORD
    (EP-test precedent): on this toolchain the composed step keeps the
    per-layer weight all-gathers over the DATA-axis pairs (the ZeRO-3
    signature) and the tp all-reduce over the tensor cosets; the grad
    sync lowers as all-reduce-keep-shard rather than a literal
    reduce-scatter at this scale, so the contract pinned here is that
    collectives form peer groups over BOTH axes — a regression to a
    single-axis layout (replicated weights or lost tp sync) fails."""
    import re
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (28, 28, 1)).astype("float32"),
                      float(rng.integers(1, 11))) for _ in range(16)]
    ds = DataSet.array(samples, distributed=True) >> SampleToBatch(16)
    m = nn.Sequential()
    m.add(nn.Reshape((49, 16)))
    m.add(nn.TransformerEncoderLayer(16, 4, 32))
    m.add(nn.Select(2, 1))
    m.add(nn.Linear(16, 10)).add(nn.LogSoftMax())
    opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion(),
                          topology=MeshTopology(data=2, tensor=4),
                          sync_mode="fsdp")
    opt.set_optim_method(SGD(learningrate=0.1))
    step = opt._build_step()
    params = m.parameter_tree()
    buffers = m.buffer_tree()
    opt_state = opt._init_opt_state(params)
    params, buffers, opt_state = opt._place_state(params, buffers, opt_state)
    txt = step.lower(params, buffers, opt_state, jax.random.key(0),
                     jnp.zeros((16, 28, 28, 1)),
                     jnp.ones((16,))).compile().as_text()
    assert "all-reduce" in txt, "fsdp x tp lost the tp partial-product sync"
    # data-axis weight gathers: the per-layer ZeRO-3 gathers, grouped over
    # the data pairs {0,4}/{1,5}/... (iota form [4,2]<=[2,4]T(1,0))
    gathers = " ".join(
        sorted(set(re.findall(r"all-gather\S*\([^\n]*?(replica_groups=\S+)",
                              txt))))
    assert ("[4,2]<=[2,4]T(1,0)" in gathers or "{0,4}" in gathers), \
        "no weight all-gather over the data-axis pairs: " + gathers
    groups = " ".join(sorted(set(re.findall(r"replica_groups=\S+", txt))))
    # tensor cosets {0..3}/{4..7} on the (data=2, tensor=4) mesh
    assert ("[2,4]<=[8]" in groups or "{0,1,2,3},{4,5,6,7}" in groups), \
        "no collective over the tensor-axis cosets: " + groups


def test_dp_tp_sp_regions_no_involuntary_rematerialization(capfd):
    """dp x tp with Megatron SP regions must transition activations from
    the dp sharding into the seq-over-tensor regions WITHOUT XLA's
    "involuntary full rematerialization" fallback (replicate-then-reshard
    — a real bandwidth tax on a pod). Round-3 regression: sp_constrain
    forced the batch dim replicated, fighting the upstream dp sharding on
    every block boundary. capfd sees the C++ SPMD partitioner's warning
    on fd 2, so the compile itself is the assertion."""
    from bigdl_tpu.parallel.tensor_parallel import enable_sequence_parallel
    rng = np.random.default_rng(0)
    samples = [Sample(rng.normal(0, 1, (28, 28, 1)).astype("float32"),
                      float(rng.integers(1, 11))) for _ in range(16)]
    ds = DataSet.array(samples, distributed=True) >> SampleToBatch(16)
    m = nn.Sequential()
    m.add(nn.Reshape((49, 16))).add(nn.Narrow(1, 1, 48))
    m.add(nn.TransformerEncoderLayer(16, 4, 32))
    m.add(nn.Select(2, 1))
    m.add(nn.Linear(16, 10)).add(nn.LogSoftMax())
    topo = MeshTopology(data=2, tensor=4)
    enable_sequence_parallel(m, topo.build())
    opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), topology=topo)
    opt.set_optim_method(SGD(learningrate=0.1))
    step = opt._build_step()
    params = m.parameter_tree()
    buffers = m.buffer_tree()
    opt_state = opt._init_opt_state(params)
    params, buffers, opt_state = opt._place_state(params, buffers, opt_state)
    capfd.readouterr()  # drop anything logged before the compile
    step.lower(params, buffers, opt_state, jax.random.key(0),
               jnp.zeros((16, 28, 28, 1)), jnp.ones((16,))).compile()
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, (
        "tp plane reintroduced a replicate-then-reshard transition:\n"
        + err[:2000])


def test_sp_constrain_preserves_batch_axis():
    """The SP-region spec must keep the batch dim on the data axis (None
    would force replication at every region boundary)."""
    from bigdl_tpu.parallel.tensor_parallel import enable_sequence_parallel
    m = nn.Sequential().add(nn.TransformerEncoderLayer(16, 4, 32))
    mesh = MeshTopology(data=2, tensor=4).build()
    assert enable_sequence_parallel(m, mesh) == 1
    layer = m._modules["0"]
    _, axis, seq_dim, batch, batch_dim = layer._sp
    assert (axis, seq_dim) == ("tensor", 1)
    assert (batch, batch_dim) == ("data", 0)
