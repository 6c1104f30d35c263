"""Model families. Family dispatch: the engine asks for (init, forward) by
ModelConfig.family so new architectures plug in without engine changes."""
from .config import ModelConfig, PRESETS, get_preset


def forward_fn(config: ModelConfig):
    """The forward callable for a family, uniform signature:
    (params, config, tokens, lengths, cache, active=None) → (logits, cache)."""
    if config.cross_decoder:        # three runs of pairs, not one period
        from . import sambay
        return sambay.forward
    if config.layer_period:         # the period families
        from . import hybrid
        return hybrid.forward
    if config.is_moe:
        from . import mixtral
        return mixtral.forward
    from . import llama
    return llama.forward


def init_fn(config: ModelConfig):
    """Random-init callable for a family: (config, key, dtype) → params."""
    if config.cross_decoder:
        from . import sambay
        return sambay.init_params
    if config.layer_period:
        from . import hybrid
        return hybrid.init_params
    if config.is_moe:
        from . import mixtral
        return mixtral.init_params
    from . import llama
    return llama.init_params


__all__ = ["ModelConfig", "PRESETS", "get_preset", "forward_fn", "init_fn"]
