"""paddle.nn equivalent."""
from . import functional  # noqa: F401
from . import utils  # noqa: F401
from . import initializer  # noqa: F401
from .layer import Layer, ParamAttr, Parameter  # noqa: F401
from .layers.activation import (  # noqa: F401
    CELU, ELU, GELU, GLU, SELU, Hardshrink, Hardsigmoid, Hardswish, Hardtanh,
    LeakyReLU, LogSigmoid, LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6, RReLU,
    Sigmoid, Silu, SiLU, Softmax, Softmax2D, Softplus, Softshrink, Softsign,
    Swish, Tanh, Tanhshrink, ThresholdedReLU,
)
from .layers.common import (  # noqa: F401
    AlphaDropout, Bilinear, CosineSimilarity, Dropout, Dropout2D, Dropout3D,
    Embedding, Flatten, Fold, Identity, Linear, Pad1D, Pad2D, Pad3D,
    PairwiseDistance, PixelShuffle, SpectralNorm, Unfold, Upsample,
    UpsamplingBilinear2D, UpsamplingNearest2D, ZeroPad2D,
)
from .layers.container import LayerDict, LayerList, ParameterList, Sequential  # noqa: F401
from .layers.conv_pool import (  # noqa: F401
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D, AdaptiveMaxPool1D,
    AdaptiveMaxPool2D, AdaptiveMaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    Conv1D, Conv1DTranspose, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose,
    MaxPool1D, MaxPool2D, MaxPool3D, MaxUnPool1D, MaxUnPool2D, MaxUnPool3D,
)
from .layers.loss import (  # noqa: F401
    BCELoss, BCEWithLogitsLoss, CosineEmbeddingLoss, CrossEntropyLoss, CTCLoss,
    HingeEmbeddingLoss, HSigmoidLoss, KLDivLoss, L1Loss, MarginRankingLoss,
    MSELoss, NLLLoss, SmoothL1Loss,
)
from .layers.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, GroupNorm, InstanceNorm1D,
    InstanceNorm2D, InstanceNorm3D, LayerNorm, LocalResponseNorm, RMSNorm,
    SyncBatchNorm,
)
from .layers.routed_experts import RoutedExperts  # noqa: F401
from .layers.transformer import (  # noqa: F401
    MultiHeadAttention, Transformer, TransformerDecoder, TransformerDecoderLayer,
    TransformerEncoder, TransformerEncoderLayer,
)
from .layers.rnn import (  # noqa: F401
    GRU, LSTM, RNN, BiRNN, GRUCell, LSTMCell, RNNCellBase, SimpleRNN,
    SimpleRNNCell,
)
from .layers.decode import BeamSearchDecoder, Decoder, dynamic_decode, gather_tree  # noqa: F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
