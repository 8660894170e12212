from hetu_tpu.models.bailing_hybrid.config import (  # noqa: F401
    BailingHybridConfig)
from hetu_tpu.models.bailing_hybrid.model import (  # noqa: F401
    BailingHybridLMHeadModel, GatedMLAttention, KDAttention)
