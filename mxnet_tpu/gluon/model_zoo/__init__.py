"""Model zoo (reference: python/mxnet/gluon/model_zoo/)."""

from . import vision
from . import bert
from . import gpt
from . import ssd
from .ssd import SSD, SSDTrainLoss, ssd_detect
from .bert import (BERTModel, BERTPretrainLoss, TransformerEncoder,
                   TransformerEncoderLayer, bert_base, bert_large,
                   bert_tiny)
from .gpt import (GPTModel, GPTLMLoss, gpt2_small, gpt2_medium,
                  gpt_tiny, CachedDecoder)
from .model_store import get_model_file, purge
from . import transformer
