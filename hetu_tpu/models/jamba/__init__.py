from hetu_tpu.models.jamba.config import JambaConfig  # noqa: F401
from hetu_tpu.models.jamba.model import (  # noqa: F401
    JambaAttention, JambaLMHeadModel)
