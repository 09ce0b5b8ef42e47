"""paddle_tpu.nn — the user-facing layer API (parity: ``paddle.nn``)."""

from . import functional, initializer
from .common import (GELU, Dropout, Embedding, GroupNorm, Identity,
                     LayerNorm, Linear, ReLU, RMSNorm, Sigmoid, SiLU,
                     Softmax, Tanh)
from .conv import AvgPool2D, Conv2D, MaxPool2D
from .layers_breadth import *  # noqa: F401,F403
from .layers_breadth import __all__ as _breadth_all
from .rnn import (GRU, LSTM, GRUCell, LSTMCell, SimpleRNN,
                  SimpleRNNCell)
from .layer import (Layer, LayerList, Parameter, Sequential,
                    abstract_parameters, functional_call)
from .transformer import (FeedForward, MultiHeadAttention, TransformerEncoder,
                          TransformerEncoderLayer)

__all__ = [
    "functional", "initializer", "Layer", "LayerList", "Parameter",
    "Sequential", "functional_call", "abstract_parameters", "Linear", "Embedding", "Dropout",
    "ReLU", "GELU", "SiLU", "Sigmoid", "Tanh", "Softmax", "LayerNorm",
    "RMSNorm", "GroupNorm", "Identity", "Conv2D", "MaxPool2D", "AvgPool2D",
    "MultiHeadAttention", "TransformerEncoderLayer", "TransformerEncoder",
    "FeedForward",
    # round-4 breadth
    "SimpleRNN", "LSTM", "GRU", "SimpleRNNCell", "LSTMCell", "GRUCell",
] + list(_breadth_all)
