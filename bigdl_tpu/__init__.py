"""bigdl_tpu — a TPU-native deep-learning framework with BigDL's capabilities.

A ground-up rebuild of BigDL's feature surface (Torch-style layer zoo,
DataSet/Transformer pipeline, Optimizer facade with triggers/validation,
distributed synchronous SGD, TensorBoard summaries, checkpoint/resume,
Torch/Caffe import) designed TPU-first:

- compute is JAX/XLA: every training/inference step is a traced, jit-compiled
  SPMD program (vs. the reference's interpreted per-layer JVM execution,
  reference ``optim/DistriOptimizer.scala``),
- distribution is a `jax.sharding.Mesh` + XLA collectives over ICI/DCN
  (vs. the reference's Spark BlockManager all-reduce,
  reference ``parameters/AllReduceParameter.scala``),
- hot ops lower to the MXU via XLA or Pallas kernels (vs. MKL JNI,
  reference ``tensor/TensorNumeric.scala``).

Public surface mirrors the reference's (``com.intel.analytics.bigdl``):

    import bigdl_tpu as bt
    model = bt.nn.Sequential()(...)
    opt = bt.optim.Optimizer(model, dataset, bt.nn.ClassNLLCriterion())
    opt.set_end_when(bt.optim.Trigger.max_epoch(10)).optimize()
"""

from bigdl_tpu.utils.engine import Engine, compile_cache_dir
from bigdl_tpu.utils.table import Table, T
from bigdl_tpu.tensor import Tensor
from bigdl_tpu import nn
from bigdl_tpu import optim
from bigdl_tpu import dataset
from bigdl_tpu import parallel
from bigdl_tpu import utils
from bigdl_tpu import visualization
from bigdl_tpu import interop
from bigdl_tpu import ml
from bigdl_tpu import telemetry

__version__ = "0.1.0"

compile_cache_dir()  # before anything compiles: see utils/engine.py

__all__ = [
    "Engine", "compile_cache_dir", "Table", "T", "Tensor",
    "nn", "optim", "dataset", "parallel", "utils", "visualization", "interop",
    "ml", "telemetry",
    "__version__",
]
