"""Flagship model families, TPU-first.

Reference analogs: PaddleNLP-style LLaMA/GPT used by the reference's auto-parallel
end-to-end tests (test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py,
test/collective/fleet hybrid suites). These are the models the framework's parallelism
stack is validated and benchmarked on.
"""
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaForCausalLMPipe,
    LlamaModel,
    LlamaPretrainingCriterion,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertModel,
    BertPretrainingCriterion,
    ErnieConfig,
    ErnieForPretraining,
    ErnieModel,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
)
from .llama_decode import LlamaDecodeEngine  # noqa: F401
from .radix_cache import PrefixCache  # noqa: F401
from .serving import AdmissionTimeout, ContinuousBatchingEngine  # noqa: F401
