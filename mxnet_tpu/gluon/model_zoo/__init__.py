"""Model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision
from .vision import get_model
from . import bert
from .bert import (
    BERTModel, BERTEncoder, get_bert_model, bert_12_768_12, bert_6_512_8,
    bert_3_64_2,
)
from . import wide_deep as wide_deep_mod
from .wide_deep import WideDeep, wide_deep
from . import gpt
from .gpt import GPTModel, gpt_mini, gpt_small
from . import deepseek
from .deepseek import DeepseekV3Model
from . import keye
from .keye import KeyeVL2Model
from . import lfm2
from .lfm2 import Lfm2MoeModel
from . import smallthinker
from .smallthinker import SmallThinkerModel
from . import granite_hybrid
from .granite_hybrid import GraniteHybridModel
from . import solar_open2
from .solar_open2 import SolarOpen2Model

__all__ = ["vision", "get_model", "bert", "BERTModel", "BERTEncoder",
           "get_bert_model", "bert_12_768_12", "bert_6_512_8",
           "bert_3_64_2", "WideDeep", "wide_deep",
           "gpt", "GPTModel", "gpt_mini", "gpt_small",
           "deepseek", "DeepseekV3Model", "keye", "KeyeVL2Model",
           "lfm2", "Lfm2MoeModel", "smallthinker", "SmallThinkerModel",
           "granite_hybrid", "GraniteHybridModel",
           "solar_open2", "SolarOpen2Model"]
