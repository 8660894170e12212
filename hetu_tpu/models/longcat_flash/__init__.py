from hetu_tpu.models.longcat_flash.config import LongCatFlashConfig  # noqa: F401
from hetu_tpu.models.longcat_flash.model import (  # noqa: F401
    LongCatFlashLMHeadModel)
