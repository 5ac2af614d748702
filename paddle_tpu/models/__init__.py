"""paddle_tpu.models — the transformer model zoo (flagship benchmark models).

The reference ships its LLM zoo out-of-tree (PaddleNLP); the BASELINE.json
north-star configs (GPT-3 1.3B DP+TP, Llama-2 7B 4D hybrid, BERT-base) make
these first-class here. Vision models live in paddle_tpu.vision.models.
"""
from .gpt import GPTConfig, GPTForCausalLM, GPTModel, gpt_tiny, gpt3_1_3b  # noqa: F401

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny", "gpt3_1_3b"]
from .llama import (  # noqa: F401,E402
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_tiny,
)

__all__ += ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny"]
from .olmo_hybrid import (  # noqa: F401,E402
    OlmoHybridConfig, OlmoHybridForCausalLM, OlmoHybridModel,
)

__all__ += ["OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM"]
from .bert import (  # noqa: F401,E402
    BertConfig, BertForPretraining, BertForSequenceClassification, BertModel,
    bert_base, bert_tiny,
)

__all__ += ["BertConfig", "BertModel", "BertForPretraining",
            "BertForSequenceClassification", "bert_tiny", "bert_base"]
from .convert_hf import load_hf_llama, load_hf_gpt2, load_hf_bert  # noqa: F401,E402

__all__ += ["load_hf_llama", "load_hf_gpt2", "load_hf_bert"]
