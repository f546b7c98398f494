"""Model zoo (ecosystem parity: PaddleNLP model families re-designed
TPU-first; SURVEY.md notes the driver configs require Llama/ERNIE-BERT/
ResNet/SD-UNet capabilities even though their code lives outside the
reference core repo)."""
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM, LlamaPretrainingCriterion
from .llama_pipe import LlamaForCausalLMPipe
from .bert import (BertConfig, BertModel, BertForSequenceClassification,
                   BertForTokenClassification, BertForQuestionAnswering,
                   BertForMaskedLM, BertForPretraining)
from .gpt import GPTConfig, GPTModel, GPTForCausalLM
from .ernie import (ErnieConfig, ErnieModel, ErnieForSequenceClassification,
                    ErnieForTokenClassification, ErnieForQuestionAnswering)
from .granite_hybrid import (GraniteMoeHybridConfig,
                             GraniteMoeHybridForCausalLM)
from .keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM
from .ling_hybrid import LingHybridConfig, LingHybridForCausalLM
from .glm_moe_dsa import GlmMoeDsaConfig, GlmMoeDsaForCausalLM
from .openpangu_moe import OpenPanguMoEConfig, OpenPanguMoEForCausalLM
from .xing_moe import XingMoEConfig, XingMoEForCausalLM
