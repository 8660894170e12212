from hetu_tpu.models.mimo_v2.config import MiMoV2Config  # noqa: F401
from hetu_tpu.models.mimo_v2.model import (MiMoAttention,  # noqa: F401
                                           MiMoV2LMHeadModel)
