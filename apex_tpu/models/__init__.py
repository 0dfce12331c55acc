"""Flagship models (ref: apex/transformer/testing/standalone_{gpt,bert}.py,
examples/imagenet) re-built TPU-native on the apex_tpu transformer stack."""

from apex_tpu.models.gpt import GPTModel, gpt_loss_fn, gpt_mtp_loss_fn
from apex_tpu.models.generate import generate
from apex_tpu.models.hf_import import (
    gpt2_from_hf,
    llama_from_hf,
    mistral_from_hf,
    params_to_hf_gpt2,
    params_to_hf_llama,
)
from apex_tpu.models.bert import BertModel
from apex_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
    cross_entropy_loss,
)

__all__ = [
    "GPTModel",
    "generate",
    "gpt2_from_hf",
    "llama_from_hf",
    "mistral_from_hf",
    "params_to_hf_gpt2",
    "params_to_hf_llama",
    "BertModel",
    "gpt_loss_fn",
    "gpt_mtp_loss_fn",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "cross_entropy_loss",
]
