"""Model zoo (the PaddleNLP/PaddleMIX-config analog for the BASELINE set:
LLaMA #4, ERNIE #3, SD UNet #5; ResNet/ViT live in vision.models)."""
from . import llama  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, build_functional_llama  # noqa: F401
from . import afmoe  # noqa: F401
from .afmoe import AfmoeConfig, build_functional_afmoe  # noqa: F401
from . import ernie  # noqa: F401
from .ernie import ErnieConfig, ErnieModel, ErnieForMaskedLM  # noqa: F401
from . import unet  # noqa: F401
from .unet import UNetConfig, UNet2DConditionModel  # noqa: F401
