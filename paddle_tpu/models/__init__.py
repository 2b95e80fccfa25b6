"""Model zoo (the PaddleNLP/PaddleMIX-config analog for the BASELINE set:
LLaMA #4, ERNIE #3, SD UNet #5; ResNet/ViT live in vision.models).

What `inference.paged.ServingEngine` serves is a configuration's
``paged_family()`` (`paged_family.PagedFamily`, the one seam): ``llama``
(`LlamaConfig`: dense GQA decoders; every engine feature) and ``nemotron_h``
(`NemotronHConfig`: Mamba-2 + attention + LatentMoE; recurrent state beside
the K/V pages, so no prefix cache, and ``speculative``, ``quantize``,
``kv_dtype``, ``mesh``, ``snapshot("full_kv")``, ``export_kv`` / ``import_kv``
are refused) and ``mla_moe`` (`MlaMoeConfig`: latent attention +
sigmoid-routed SwiGLU experts; ONE store of compressed rows, every layer's
state pages, so the prefix cache and the page transfers work; ``speculative``,
``quantize``, ``kv_dtype``, ``mesh`` are refused) and ``sambay``
(`models.sambay.SambaYConfig`, imported from its module: Mamba-1 + window and
full differential attention + Gated Memory Units; ONE K/V page store that
eight layers read, a window ring and a per-channel state a slot; refuses what
``nemotron_h`` refuses).  AFMoE runs through the
compiled train step only: its window layers need a page table a layer kind in
the cache (ROADMAP B4)."""
from . import llama  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, build_functional_llama  # noqa: F401
from . import afmoe  # noqa: F401
from .afmoe import AfmoeConfig, build_functional_afmoe  # noqa: F401
from . import nemotron_h  # noqa: F401
from .nemotron_h import NemotronHConfig, build_functional_nemotron_h  # noqa: F401
from . import mla_moe  # noqa: F401
from .mla_moe import MlaMoeConfig, build_functional_mla_moe  # noqa: F401
from .paged_family import PagedFamily  # noqa: F401
from . import ernie  # noqa: F401
from .ernie import ErnieConfig, ErnieModel, ErnieForMaskedLM  # noqa: F401
from . import unet  # noqa: F401
from .unet import UNetConfig, UNet2DConditionModel  # noqa: F401
