"""paddle_tpu.nn (reference: python/paddle/nn/)."""
from .layer_base import Layer, ParamAttr  # noqa: F401
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .clip import (ClipGradByValue, ClipGradByNorm,  # noqa: F401
                   ClipGradByGlobalNorm, clip_grad_norm_)
from .layer.container import (Sequential, LayerList, ParameterList,  # noqa: F401
                              LayerDict)
from .layer.common import (Identity, Linear, Dropout, Dropout2D, Dropout3D,  # noqa: F401
                           AlphaDropout, Embedding, Flatten, Upsample,
                           UpsamplingNearest2D, UpsamplingBilinear2D, Pad1D,
                           Pad2D, Pad3D, ZeroPad2D, CosineSimilarity,
                           PairwiseDistance, Bilinear, PixelShuffle,
                           PixelUnshuffle, ChannelShuffle, Unfold, Fold)
from .layer.activation import (ReLU, ReLU6, GELU, Sigmoid, Tanh, LeakyReLU,  # noqa: F401
                               ELU, CELU, SELU, Silu, Swish, Mish, Hardswish,
                               Hardsigmoid, Hardtanh, Hardshrink, Softshrink,
                               Softplus, Softsign, Tanhshrink, ThresholdedReLU,
                               LogSigmoid, Softmax, LogSoftmax, Maxout, PReLU,
                               RReLU, GLU)
from .layer.conv import (Conv1D, Conv2D, Conv3D, Conv1DTranspose,  # noqa: F401
                         Conv2DTranspose, Conv3DTranspose)
from .layer.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,  # noqa: F401
                         SyncBatchNorm, LayerNorm, RMSNorm, GroupNorm,
                         InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
                         LocalResponseNorm, SpectralNorm)
from .layer.pooling import (AvgPool1D, AvgPool2D, AvgPool3D, MaxPool1D,  # noqa: F401
                            MaxPool2D, MaxPool3D, AdaptiveAvgPool1D,
                            AdaptiveAvgPool2D, AdaptiveAvgPool3D,
                            AdaptiveMaxPool1D, AdaptiveMaxPool2D,
                            AdaptiveMaxPool3D)
from .layer.loss import (CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss,  # noqa: F401
                         BCEWithLogitsLoss, KLDivLoss, SmoothL1Loss,
                         MarginRankingLoss, HingeEmbeddingLoss,
                         CosineEmbeddingLoss, TripletMarginLoss, CTCLoss)
from .layer.rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN,  # noqa: F401
                        BiRNN, SimpleRNN, LSTM, GRU)
from .layer.transformer import (MultiHeadAttention, TransformerEncoderLayer,  # noqa: F401
                                TransformerEncoder, TransformerDecoderLayer,
                                TransformerDecoder, Transformer)
from .layer.moe import MoELayer, HeldExperts  # noqa: F401
from .decode import (Decoder, BeamSearchDecoder, dynamic_decode,  # noqa: F401
                     gather_tree)
from . import utils  # noqa: F401,E402
# era-importable submodule aliases (reference nn/__init__.py:18-21 +
# 158-160 binds layer.{norm,common,rnn,loss,conv,vision} and
# functional.extension as paddle.nn attributes)
from .layer import common, conv, loss, norm, rnn, vision  # noqa: F401,E402
from .functional import extension  # noqa: F401,E402
from .legacy_layers import (HSigmoidLoss, NCELoss, RowConv, Pool2D,  # noqa: F401,E402
                            StaticRNN, BilinearTensorProduct,
                            ctc_greedy_decoder, clip_by_norm, nce)
