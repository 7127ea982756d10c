from .gpt import (  # noqa: F401
    GPTConfig, GPTForPretraining, GPTForPretrainingPipe, GPTModel, gpt_tiny,
    gpt_1p3b, gpt_345m,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig, AfmoeForCausalLM, AfmoeModel, afmoe_tiny,
)
from .olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig, OlmoHybridForCausalLM, OlmoHybridModel, olmo_hybrid_tiny,
)
from .deepseek_v2 import (  # noqa: F401
    DeepseekV2Config, DeepseekV2ForCausalLM, DeepseekV2Model,
    deepseek_v2_tiny,
)
from .sdar import (  # noqa: F401
    SdarConfig, SdarForCausalLM, SdarModel, sdar_tiny,
)
from .ernie import (  # noqa: F401
    BertConfig, BertForPretraining, BertModel, ErnieConfig, ErnieForPretraining,
    ErnieModel, bert_base, bert_large, ernie_base, ernie_large, ernie_tiny,
)
from .rec import DeepFM, WideDeep, ctr_loss  # noqa: F401
