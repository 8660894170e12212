from hetu_tpu.models.phi4_flash.config import Phi4FlashConfig  # noqa: F401
from hetu_tpu.models.phi4_flash.model import (  # noqa: F401
    DiffAttention, GatedMemoryUnit, MambaMixer, Phi4FlashLMHeadModel)
