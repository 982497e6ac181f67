from .attention import SelfAttentionLayer
from .base import LAYER_REGISTRY, LayerConf, register_layer
from .decoder import (AttentionLayer, GatedMLPLayer, LatentAttentionLayer,
                      LMHeadLayer, MoELayer, ProjectionLayer, RMSNormLayer,
                      SparseAttentionLayer, TokenEmbeddingLayer)
from .convolution import (ConvolutionLayer, GlobalPoolingLayer,
                          SubsamplingLayer, ZeroPaddingLayer)
from .feedforward import (ActivationLayer, AutoEncoder, DenseLayer,
                          DropoutLayer, EmbeddingLayer, LossLayer, OutputLayer,
                          RnnOutputLayer)
from .mamba2 import Mamba2Layer
from .normalization import BatchNormalization, LocalResponseNormalization
from .rbm import RBM
from .recurrent import (BaseRecurrentLayer, GravesBidirectionalLSTM,
                        GravesLSTM, SimpleRnn)
from .variational import (BernoulliReconstructionDistribution,
                          GaussianReconstructionDistribution,
                          VariationalAutoencoder)

__all__ = [
    "LAYER_REGISTRY", "LayerConf", "register_layer",
    "ActivationLayer", "AutoEncoder", "DenseLayer", "DropoutLayer",
    "EmbeddingLayer", "LossLayer", "OutputLayer", "RnnOutputLayer",
    "ConvolutionLayer", "SubsamplingLayer", "ZeroPaddingLayer",
    "GlobalPoolingLayer", "BatchNormalization", "LocalResponseNormalization",
    "BaseRecurrentLayer", "GravesLSTM", "GravesBidirectionalLSTM", "SimpleRnn",
    "SelfAttentionLayer", "TokenEmbeddingLayer", "RMSNormLayer",
    "SparseAttentionLayer", "AttentionLayer", "LatentAttentionLayer",
    "ProjectionLayer", "GatedMLPLayer", "MoELayer", "Mamba2Layer",
    "LMHeadLayer", "RBM", "VariationalAutoencoder",
    "BernoulliReconstructionDistribution",
    "GaussianReconstructionDistribution",
]
