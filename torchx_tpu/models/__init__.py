from torchx_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    forward,
    init_params,
    loss_fn,
    param_specs,
    shard_params,
)


def all_configs() -> dict:
    """Dense llama presets plus the MoE family (models/moe.py)."""
    from torchx_tpu.models import llama, moe

    return {**llama.CONFIGS, **moe.CONFIGS}
