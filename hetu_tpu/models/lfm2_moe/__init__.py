from hetu_tpu.models.lfm2_moe.config import Lfm2MoeConfig  # noqa: F401
from hetu_tpu.models.lfm2_moe.model import (  # noqa: F401
    Lfm2Attention, Lfm2MoeLMHeadModel, ShortConv)
