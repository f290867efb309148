"""Model families (reference P18 model zoo + the BASELINE.md benchmark
configs: LeNet/ResNet in paddle_tpu.vision.models; BERT/ERNIE and
Transformer here)."""
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, BertPretrainingCriterion,
    ErnieModel, ErnieForPretraining, bert_base, bert_large, ernie_base,
)
from .transformer import (  # noqa: F401
    TransformerConfig, TransformerModel, CrossEntropyCriterion,
    transformer_base, transformer_big,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForGeneration, gpt_small,
)
from .static_lm import build_bert_base, build_transformer_lm  # noqa: F401
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridModel, granite_hybrid_tiny,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHModel, nemotron_h_tiny,
)
from .cohere2_moe import (  # noqa: F401
    Cohere2MoeConfig, Cohere2MoeModel, cohere2_moe_tiny,
)
from .pangu_ultra_moe import (  # noqa: F401
    PanguUltraMoeConfig, PanguUltraMoeModel, pangu_ultra_moe_tiny,
)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeModel, lfm2_moe_tiny,
)
