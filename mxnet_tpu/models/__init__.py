"""Model families for the five BASELINE workloads (LeNet/ResNet live in
gluon.model_zoo.vision; BERT/Transformer/DeepAR here)."""
from .bert import BERTModel, bert_base, bert_large, bert_tiny  # noqa: F401
from .transformer import (TransformerModel, transformer_big,  # noqa: F401
                          transformer_base, transformer_tiny)
from .deepar import DeepARNetwork, deepar  # noqa: F401
from .decoder_lm import DecoderLM  # noqa: F401
