from repro.models.config import (HybridConfig, MLAConfig, MoEConfig,
                                 ModelConfig, SSMConfig, YarnConfig)
from repro.models.model import (decode_step, forward, init_cache, init_params,
                                loss_fn, make_train_step, prefill_step)

__all__ = [
    "ModelConfig", "MLAConfig", "MoEConfig", "SSMConfig", "HybridConfig",
    "YarnConfig",
    "init_params", "forward", "loss_fn", "make_train_step",
    "init_cache", "prefill_step", "decode_step",
]
