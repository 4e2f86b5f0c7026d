"""bigdl_tpu.nn — the layer zoo (reference ``$B/nn/``, 145 files).

Everything is importable flat, mirroring the reference's single
``com.intel.analytics.bigdl.nn`` namespace:

    from bigdl_tpu import nn
    model = nn.Sequential().add(nn.Linear(784, 100)).add(nn.ReLU())
"""

from bigdl_tpu.nn.module import (
    Module, TensorModule, Activity, functional_apply, jit_apply, RngStream,
    current_rng,
)
from bigdl_tpu.nn.criterion import (
    Criterion, ClassNLLCriterion, CrossEntropyCriterion, MSECriterion,
    AbsCriterion, BCECriterion, SmoothL1Criterion, SmoothL1CriterionWithWeights,
    MarginCriterion, MarginRankingCriterion, HingeEmbeddingCriterion,
    L1HingeEmbeddingCriterion, CosineEmbeddingCriterion, DistKLDivCriterion,
    SoftMarginCriterion, MultiLabelSoftMarginCriterion, MultiMarginCriterion,
    MultiLabelMarginCriterion, ClassSimplexCriterion, DiceCoefficientCriterion,
    L1Cost, SoftmaxWithCriterion, ParallelCriterion, MultiCriterion,
    CriterionTable, TimeDistributedCriterion, FusedLMHeadCriterion,
)
from bigdl_tpu.nn.activation import (
    ReLU, ReLU6, Threshold, PReLU, RReLU, LeakyReLU, ELU, Sigmoid, LogSigmoid,
    Tanh, TanhShrink, HardTanh, HardShrink, SoftShrink, SoftPlus, SoftSign,
    SoftMax, SoftMin, LogSoftMax, Clamp, Power, Sqrt, Square, Abs, Log, Exp,
    AddConstant, MulConstant, GradientReversal,
)
from bigdl_tpu.nn.linear import (
    Linear, Bilinear, Cosine, Euclidean, MM, MV, DotProduct, LookupTable,
    Add, CAdd, Mul, CMul, Scale, LMHead, TiedLMHead,
)
from bigdl_tpu.nn.quantized import (
    quantize_model, quantize_module, quantize_array, cast_model, QuantizedLinear,
    QuantizedLMHead, QuantizedSpatialConvolution, QuantizedMultiHeadAttention,
    QuantizedLookupTable,
)
from bigdl_tpu.nn.conv import (
    SpatialConvolution, SpatialShareConvolution, SpaceToDepthConv7,
    stem_conv7, SpatialDilatedConvolution,
    SpatialFullConvolution, VolumetricConvolution, SpatialConvolutionMap,
)
from bigdl_tpu.nn.pooling import (
    SpatialMaxPooling, SpatialAveragePooling, VolumetricMaxPooling, RoiPooling,
)
from bigdl_tpu.nn.normalization import (
    BatchNormalization, SpatialBatchNormalization, VolumetricBatchNormalization,
    SpatialCrossMapLRN, Normalize, SpatialSubtractiveNormalization,
    SpatialDivisiveNormalization, SpatialContrastiveNormalization,
    InputNormalize,
)
from bigdl_tpu.nn.containers import (
    Container, Sequential, Concat, ConcatTable, ParallelTable, MapTable,
    JoinTable, SplitTable, SelectTable, NarrowTable, FlattenTable,
    CAddTable, CSubTable, CMulTable, CDivTable, CMaxTable, CMinTable,
    MixtureTable, MaskedSelect, Index, Bottle, Identity, Echo,
)
from bigdl_tpu.nn.shape import (
    Reshape, View, InferReshape, Squeeze, Unsqueeze, Transpose, Replicate,
    Padding, SpatialZeroPadding, Narrow, Select, Reverse, Contiguous,
)
from bigdl_tpu.nn.regularization import (
    Dropout, L1Penalty, Regularizer, L1Regularizer, L2Regularizer,
    L1L2Regularizer,
)
from bigdl_tpu.nn.reduce import (Sum, Mean, Max, Min, CosineDistance,
                                 PairwiseDistance)
from bigdl_tpu.nn.graph import Graph, Input, Node
from bigdl_tpu.nn.detection import Nms, nms
from bigdl_tpu.nn.recurrent import (
    Cell, RnnCell, LSTM, LSTMPeephole, GRU, Recurrent, RecurrentDecoder,
    BiRecurrent, TimeDistributed,
)
from bigdl_tpu.nn.attention import (
    LayerNorm, RMSNorm, MultiHeadAttention, LatentAttention,
    PositionalEncoding, LearnedPositionalEncoding, TransformerEncoderLayer,
    TransformerEncoder,
)
from bigdl_tpu.nn.mamba import Mamba2
from bigdl_tpu.nn.short_conv import ShortConv
from bigdl_tpu.nn.gated_delta_net import GatedDeltaNet
from bigdl_tpu.nn.hybrid import (GatedMLP, HybridBlock, HybridDecoder,
                                 MTPModule)
