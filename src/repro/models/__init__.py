from .config import ModelConfig, n_params
from .model import (decode_step, forward, init_cache, init_params, lm_loss,
                    lm_worker_loss, prefill)
from .sharding import cache_pspecs, param_pspecs
