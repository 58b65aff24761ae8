"""Model families beyond the vision zoo (reference: BERT-class transformer
workloads driven through gluon — reference configs #3/#5) plus the causal
decoder LM behind the continuous-batching decode serving tier."""
from . import bert  # noqa: F401
from .bert import BERTModel, BERTEncoder, bert_base, bert_large, bert_tiny  # noqa: F401
from . import decoder  # noqa: F401
from .decoder import CausalLM, DecoderConfig, decoder_tiny, decoder_tiny_lm  # noqa: F401
