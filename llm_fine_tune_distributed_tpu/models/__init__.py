from llm_fine_tune_distributed_tpu.models.configs import (  # noqa: F401
    PRESETS,
    get_preset,
    from_hf_config,
)
from llm_fine_tune_distributed_tpu.models.transformer import (  # noqa: F401
    init_params,
)
