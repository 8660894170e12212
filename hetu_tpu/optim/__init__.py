from hetu_tpu.optim.optimizer import (
    Optimizer, AdamW, Adam, SGD, clip_by_global_norm, zero_shardings,
    state_shardings, cosine_schedule, constant_schedule,
)
from hetu_tpu.optim.grad_scaler import GradScaler
from hetu_tpu.optim.zero_refresh import (
    quantized_zero_update, refresh_dims, refresh_specs,
)
