"""Flagship model zoo: BERT / GPT-2 / ERNIE pretraining models for the
BASELINE.md benchmark configs (#3 BERT DP, #4 ERNIE sharding, #5 GPT-2 PP),
and the decoders the serving benchmark runs (Cohere2-MoE, DeepSeek-V3's
family, EvaByte)."""
from .bert import (BertConfig, BertModel, BertForPretraining,  # noqa: F401
                   BertPretrainingCriterion,
                   BertForSequenceClassification,
                   bert_base_config, bert_large_config)
from .gpt import (GPTConfig, GPTModel, GPTForPretraining,  # noqa: F401
                  GPTPretrainingCriterion, GPTBlock,
                  gpt2_small_config, gpt2_medium_config, gpt2_large_config)
from .ernie import (ErnieConfig, ErnieModel, ErnieForPretraining,  # noqa: F401
                    ErniePretrainingCriterion,
                    ErnieForSequenceClassification,
                    ernie_base_config, ernie_large_config)
from .dlrm import (DLRMConfig, DLRM, DLRMCriterion,  # noqa: F401
                   dlrm_tiny_config)
from .cohere_moe import (CohereMoEConfig, CohereMoEBlock,  # noqa: F401
                         CohereMoEForCausalLM)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3Block,  # noqa: F401
                          DeepseekV3ForCausalLM)
from .evabyte import (EvaByteConfig, EvaByteBlock,  # noqa: F401
                      EvaByteForCausalLM)
