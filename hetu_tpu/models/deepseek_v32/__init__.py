from hetu_tpu.models.deepseek_v32.config import DeepseekV32Config  # noqa: F401
from hetu_tpu.models.deepseek_v32.model import (  # noqa: F401
    DeepseekV32LMHeadModel, DSAttention, LightningIndexer)
