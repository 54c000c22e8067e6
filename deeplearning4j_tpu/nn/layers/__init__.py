"""Layer catalog — parity with DL4J's ~45 layer types (SURVEY.md §2.1 layer
configs) plus TPU-first attention/transformer layers."""

from .attention import (MultiHeadAttention, PositionalEmbedding,
                        TransformerEncoderBlock, dot_product_attention)
from .conv import (Conv1D, Conv2D, Cropping1D, Cropping2D, Deconv2D,
                   DepthwiseConv2D,
                   SeparableConv2D, SpaceToBatch, SpaceToDepth, Subsampling1D,
                   Subsampling2D, Upsampling1D, Upsampling2D, ZeroPadding1D,
                   ZeroPadding2D)
from .core import (ActivationLayer, AlphaDropout, CenterLossOutput,
                   CnnLossLayer, Dense,
                   DropoutLayer, ElementWiseMultiplication, Embedding,
                   EmbeddingSequence, GaussianDropout, GaussianNoise,
                   LossLayer, Output, PReLU, RnnLossLayer,
                   RnnOutput)
from .custom import CustomLayer, Lambda, resolve_function
from .moe import MoE, MoETransformerBlock
from .glm4_moe_lite import Glm4MoeLiteBlock
from .laguna import LagunaBlock
from .minicpm_sala import MiniCpmSalaBlock, ScaledRMSNorm
from .norm import LRN, BatchNorm, LayerNorm, RMSNorm
from .olmoe import OlmoeBlock
from .pooling import Flatten, GlobalPooling, Reshape
from .recurrent import (GRU, LSTM, Bidirectional, GravesLSTM, LastTimeStep,
                        RecurrentLayer, SimpleRnn)
from .special import VAE, AutoEncoder, Frozen, Yolo2Output

__all__ = [
    "ActivationLayer", "AlphaDropout", "AutoEncoder", "BatchNorm",
    "Bidirectional",
    "CenterLossOutput", "CnnLossLayer", "Conv1D", "Conv2D", "Cropping1D",
    "Cropping2D",
    "CustomLayer", "Deconv2D", "Dense", "DepthwiseConv2D", "DropoutLayer",
    "ElementWiseMultiplication", "Embedding", "EmbeddingSequence",
    "GaussianDropout", "GaussianNoise", "Flatten",
    "Frozen", "GRU", "Glm4MoeLiteBlock", "GlobalPooling", "GravesLSTM", "LRN", "LSTM", "Lambda",
    "LagunaBlock", "LastTimeStep",
    "LayerNorm", "LossLayer", "MiniCpmSalaBlock", "MoE", "MoETransformerBlock",
    "MultiHeadAttention", "OlmoeBlock", "Output", "PReLU",
    "PositionalEmbedding", "RMSNorm", "RecurrentLayer", "Reshape", "RnnLossLayer", "RnnOutput",
    "ScaledRMSNorm", "SeparableConv2D", "SimpleRnn", "SpaceToBatch", "SpaceToDepth",
    "Subsampling1D", "Subsampling2D", "TransformerEncoderBlock", "Upsampling1D",
    "Upsampling2D", "VAE", "Yolo2Output", "ZeroPadding1D", "ZeroPadding2D",
]
