from hetu_tpu.models.xing4.config import Xing4Config  # noqa: F401
from hetu_tpu.models.xing4.model import Xing4LMHeadModel  # noqa: F401
