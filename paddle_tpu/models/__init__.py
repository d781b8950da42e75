from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
    ErnieConfig,
    ErnieForMaskedLM,
    ErnieForSequenceClassification,
    ErnieModel,
)
from .ernie_moe import ErnieMoEConfig, ErnieMoEForCausalLM  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
)
from .mamba import Mamba2Mixer, MambaConfig, MambaForCausalLM  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM  # noqa: F401
from .phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM  # noqa: F401
from .rwkv import RWKVConfig, RWKVForCausalLM  # noqa: F401
from .t5 import (  # noqa: F401
    T5Config,
    T5ForConditionalGeneration,
    T5Model,
)
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
from .vit import ViT, ViTConfig  # noqa: F401
