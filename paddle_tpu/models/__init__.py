"""Flagship model zoo (language models; vision lives in paddle_tpu.vision).

Reference parity: the GPT/BERT model definitions used by the reference's
fleet hybrid-parallel tests (hybrid_parallel_pp_transformer.py,
hybrid_parallel_mp_model.py patterns) and the PaddleNLP GPT that
sandyhouse/Paddle's pipeline/sharding work was built to train.
"""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM,
                  GPTPretrainingCriterion, gpt_tiny, gpt_small, gpt_medium,
                  gpt_1p3b)
from .bert import BertConfig, BertModel, BertForPretraining
from .deepfm import DeepFM, deepfm_loss  # noqa: F401,E402
from .afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: F401,E402
from .phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM  # noqa: F401,E402
from .axk1 import AxK1Config, AxK1ForCausalLM  # noqa: F401,E402
