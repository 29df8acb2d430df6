"""Model zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import vision
from . import bert
from . import ssd
from . import glm_moe_lite
from . import nemotron_h
from . import laguna
from .vision import get_model
from .bert import BERTModel, bert_12_768_12, bert_24_1024_16

__all__ = ["vision", "bert", "ssd", "glm_moe_lite", "nemotron_h", "laguna", "get_model", "BERTModel",
           "bert_12_768_12", "bert_24_1024_16"]
