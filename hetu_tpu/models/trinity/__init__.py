from hetu_tpu.models.trinity.config import TrinityConfig  # noqa: F401
from hetu_tpu.models.trinity.model import (GatedAttention,  # noqa: F401
                                           TrinityLMHeadModel)
