from hetu_tpu.models.kimi_k2.config import KimiK2Config  # noqa: F401
from hetu_tpu.models.kimi_k2.model import (KimiK2LMHeadModel,  # noqa: F401
                                           MLAttention)
