"""Model zoo — the reference's examples/ reimplemented as framework models.

Reference: examples/cnn (ResNet/VGG/LeNet/MLP), examples/nlp (BERT),
examples/moe, examples/ctr (Wide&Deep etc.), tools/Galvatron (gpt/llama).
"""

from hetu_tpu.models.resnet import BasicBlock, ResNet, ResNet18, ResNet34
from hetu_tpu.models.mlp import MLP
from hetu_tpu.models.bert import BertConfig, BertModel, bert_base, bert_large
from hetu_tpu.models.gpt import GPTConfig, GPTModel, gpt2_small
from hetu_tpu.models.cnn_zoo import LeNet, VGG
from hetu_tpu.models.gcn import GCN
from hetu_tpu.models.wdl import WideDeep
from hetu_tpu.models.gpt_hetero import HeteroGPT, PlanStrategy
from hetu_tpu.models.ctr_zoo import DeepFM, DCN, CrossNet
from hetu_tpu.models.llama import (HeteroLlama, LlamaConfig, LlamaModel,
                                   llama2_7b)
from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
from hetu_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model
