"""gluon.model_zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import vision
from .vision import get_model
from . import bert
from .bert import bert_base, bert_large, BERTModel, BERTForPretraining
from . import rnn_lm
from .rnn_lm import RNNModel
from . import gpt
from .gpt import GPTModel, gpt2_small, gpt2_medium, gpt_tiny
from . import qwen3_next as _qwen3_next
from .qwen3_next import Qwen3NextModel, qwen3_next, qwen3_next_tiny
from . import granite_hybrid as _granite_hybrid
from .granite_hybrid import (GraniteHybridModel, granite_hybrid,
                             granite_hybrid_tiny)
from . import axk1 as _axk1
from .axk1 import AXK1Model, axk1, axk1_tiny

__all__ = ["vision", "get_model", "bert", "bert_base", "bert_large",
           "gpt", "GPTModel", "gpt2_small", "gpt2_medium", "gpt_tiny",
           "BERTModel", "BERTForPretraining", "rnn_lm", "RNNModel",
           "Qwen3NextModel", "qwen3_next", "qwen3_next_tiny",
           "GraniteHybridModel", "granite_hybrid", "granite_hybrid_tiny",
           "AXK1Model", "axk1", "axk1_tiny"]
