"""Model zoo + factory.

Capability parity with `util.build_model` (reference: src/util.py:8-19),
which wires LeNet / ResNet18 / ResNet34 / ResNet50 / VGG11(bn); the README
additionally advertises deeper ResNets and the full VGG family
(reference: README.md:124), so the factory here registers all of them.
Also fixes the reference's latent bug where `ResNet34()` was called without
its required `num_classes` argument (reference: src/util.py:15 vs
src/model_ops/resnet.py:103).
"""

from __future__ import annotations

from typing import Any, Dict

from pytorch_distributed_nn_tpu.models.glm47_flash import (
    Glm47Flash,
    Glm47FlashConfig,
    glm47_flash_ep8,
    glm47_flash_tiny,
)
from pytorch_distributed_nn_tpu.models.granite4_hybrid import (
    granite4_h_micro_pp4,
    granite4_tiny,
)
from pytorch_distributed_nn_tpu.models.lenet import LeNet
from pytorch_distributed_nn_tpu.models.lfm2 import (
    Lfm2Config,
    Lfm2MoE,
    lfm2_8b_a1b_ep4,
    lfm2_tiny,
)
from pytorch_distributed_nn_tpu.models.resnet import (
    CifarResNet,
    ResNet,
    ResNet18,
    ResNet20,
    ResNet32,
    ResNet34,
    ResNet50,
    ResNet56,
    ResNet101,
    ResNet110,
    ResNet152,
)
from pytorch_distributed_nn_tpu.models.smallthinker import (
    SmallThinker,
    SmallThinkerConfig,
    smallthinker_21b_a3b_ep8,
    smallthinker_tiny,
)
from pytorch_distributed_nn_tpu.models.transformer import (
    BertMLM,
    CausalLM,
    TransformerConfig,
    TransformerEncoder,
    bert_base,
    bert_tiny,
    decode_attention,
    full_attention,
    gpt_mini,
    gpt_tiny,
)
from pytorch_distributed_nn_tpu.models.vgg import (
    VGG,
    vgg11,
    vgg11_bn,
    vgg13,
    vgg13_bn,
    vgg16,
    vgg16_bn,
    vgg19,
    vgg19_bn,
)

_REGISTRY = {
    "LeNet": lambda num_classes, **kw: LeNet(num_classes=num_classes, **kw),
    "ResNet18": ResNet18,
    "ResNet34": ResNet34,
    "ResNet50": ResNet50,
    "ResNet101": ResNet101,
    "ResNet152": ResNet152,
    # Thin CIFAR family (6n+2) — the reference README's ResNet-32/110
    # (reference: README.md:124), never defined in its model code.
    "ResNet20": ResNet20,
    "ResNet32": ResNet32,
    "ResNet56": ResNet56,
    "ResNet110": ResNet110,
    # Reference's "VGG11" means vgg11_bn (src/util.py:18-19).
    "VGG11": vgg11_bn,
    "VGG13": vgg13_bn,
    "VGG16": vgg16_bn,
    "VGG19": vgg19_bn,
    # Transformer family (BASELINE.json stretch config: BERT-base MLM).
    # num_classes is ignored — the MLM head projects to the vocabulary.
    "BertBase": bert_base,
    "BertTiny": bert_tiny,
    # Causal decoder family (ROADMAP item 2: generative serving). Same
    # blocks and partition annotations; adds the KV-cache decode mode
    # the serving/generate/ engine pre-traces.
    "GptTiny": gpt_tiny,
    "GptMini": gpt_mini,
    # LFM2-MoE decoder (gated short convolutions + grouped-query attention,
    # sparse experts): one chip's share of LFM2-8B-A1B under four-way
    # expert parallelism at published widths, and a toy of the same shape.
    # Train as causal LMs (dataset='NextTokenSynth'); no decode mode yet.
    "Lfm2_8B_A1B_EP4": lfm2_8b_a1b_ep4,
    "Lfm2Tiny": lfm2_tiny,
    # SmallThinker sparse-expert decoder (window layers with rotary
    # positions and global layers without, a softmax router that reads the
    # layer's input, ReLU-gated experts, an untied head): one chip's share
    # of SmallThinker-21BA3B under eight-way expert parallelism at
    # published widths, and a toy of the same shape. Training only.
    "SmallThinker_21B_A3B_EP8": smallthinker_21b_a3b_ep8,
    "SmallThinkerTiny": smallthinker_tiny,
    # GLM-4.7-Flash sparse-expert decoder (multi-head latent attention, a
    # shared expert beside the routed ones, a scaled sigmoid router, one
    # next-token prediction module in the loss): one chip's share of
    # GLM-4.7-Flash under eight-way expert parallelism at published
    # widths, and a toy of the same shape. Training only.
    "GLM47_Flash_EP8": glm47_flash_ep8,
    "GLM47FlashTiny": glm47_flash_tiny,
    # Granite-4.0-H hybrid decoder (Mamba-2 layers through the chunked
    # state-space scan kernel, ops/ssd.py, beside NoPE grouped-query
    # attention; Granite's multipliers, a tied head): one chip's share of
    # pipeline stage 1 of Granite-4.0-H-Micro at published widths, and a
    # toy of the same shape. Training only.
    "Granite4_H_Micro_PP4": granite4_h_micro_pp4,
    "Granite4Tiny": granite4_tiny,
    "VGG11NoBN": vgg11,
    "VGG13NoBN": vgg13,
    "VGG16NoBN": vgg16,
    "VGG19NoBN": vgg19,
}

# Input spec per model family: (height, width, channels) for the canonical
# dataset (MNIST for LeNet, 32x32 RGB for the rest — reference pairs LeNet
# with MNIST and ResNet/VGG with CIFAR/SVHN, src/run_pytorch.sh:1-16).
INPUT_SPECS: Dict[str, Any] = {"LeNet": (28, 28, 1)}
_DEFAULT_INPUT_SPEC = (32, 32, 3)

# Text models take (L,) int32 token inputs instead of images; callers branch
# on membership here (e.g. the trainer and __graft_entry__).
TEXT_MODELS = {"BertBase", "BertTiny", "GptTiny", "GptMini",
               "Lfm2_8B_A1B_EP4", "Lfm2Tiny",
               "SmallThinker_21B_A3B_EP8", "SmallThinkerTiny",
               "GLM47_Flash_EP8", "GLM47FlashTiny",
               "Granite4_H_Micro_PP4", "Granite4Tiny"}
INPUT_SPECS["BertBase"] = (512,)
INPUT_SPECS["BertTiny"] = (128,)
INPUT_SPECS["GptTiny"] = (64,)
INPUT_SPECS["GptMini"] = (128,)
INPUT_SPECS["Lfm2_8B_A1B_EP4"] = (8192,)
INPUT_SPECS["Lfm2Tiny"] = (64,)
INPUT_SPECS["SmallThinker_21B_A3B_EP8"] = (16384,)
INPUT_SPECS["SmallThinkerTiny"] = (64,)
INPUT_SPECS["GLM47_Flash_EP8"] = (4096,)
INPUT_SPECS["GLM47FlashTiny"] = (64,)
INPUT_SPECS["Granite4_H_Micro_PP4"] = (4096,)
INPUT_SPECS["Granite4Tiny"] = (64,)

# Causal decoders: artifacts of these networks serve the generative path
# (serving/generate/) — POST /v1/generate instead of /v1/infer.
GENERATIVE_MODELS = {"GptTiny", "GptMini"}


def is_text_model(model_name: str) -> bool:
    return model_name in TEXT_MODELS


def is_generative_model(model_name: str) -> bool:
    return model_name in GENERATIVE_MODELS


def model_names():
    return sorted(_REGISTRY)


def input_spec(model_name: str):
    return INPUT_SPECS.get(model_name, _DEFAULT_INPUT_SPEC)


def build_model(model_name: str, num_classes: int = 10, **kwargs):
    """Instantiate a model by its CLI name.

    Unlike the reference factory — which silently returns None for unknown
    names (src/util.py:8-19 has no else branch) — unknown names raise.
    """
    try:
        factory = _REGISTRY[model_name]
    except KeyError:
        raise ValueError(
            f"unknown model {model_name!r}; available: {model_names()}"
        ) from None
    return factory(num_classes=num_classes, **kwargs)
