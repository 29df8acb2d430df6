"""Gluon nn layers (reference: python/mxnet/gluon/nn/)."""
from ..block import Block, HybridBlock, SymbolBlock
from .basic_layers import *     # noqa: F401,F403
from .conv_layers import *      # noqa: F401,F403
from .transformer_layers import *   # noqa: F401,F403
from . import basic_layers, conv_layers, transformer_layers

__all__ = (["Block", "HybridBlock", "SymbolBlock"] +
           basic_layers.__all__ + conv_layers.__all__
           + transformer_layers.__all__)
