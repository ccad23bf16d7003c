"""Reference model implementations used by benchmarks and examples.

LeNet (ref: example/gluon/mnist), BERT-base (GluonNLP recipe — the north
star config), Transformer (example/gluon/transformer shape), GPT-style
causal LM (decoder-only over the flash kernel's causal path), the
configurable decoder block of sparse-expert and looped models (RMS norm
before or round each sub-layer, rotary or no positions, grouped-query
windowed or full attention, one share's experts or a dense gated
feed-forward, the stack run once or several times with an exit gate),
built on mxnet_tpu.gluon.
"""
from .lenet import LeNet
from .bert import BertModel, BertForPretraining, bert_base_config, bert_pretrain_loss
from .transformer import TransformerEncoder, TransformerModel
from .gpt import GPTModel, gpt_lm_loss, gpt2_small_config
from .decoder import (DecoderModel, DecoderBlock, SparseExperts, GatedFFN,
                      ExitGate, RMSNorm, decoder_lm_loss, looped_lm_loss)
from .ssd import SSD, ssd_512, ssd_300, ssd_train_loss
