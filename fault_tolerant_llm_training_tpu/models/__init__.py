from .configs import (
    LatentMoEConfig,
    PRESETS,
    TransformerConfig,
    get_config,
)
from .llama import Transformer


def build_model(cfg):
    """The model class a configuration belongs to, built on it: found from
    the configuration's type (``TransformerConfig`` -> ``Transformer``,
    ``LatentMoEConfig`` -> ``LatentMoETransformer``)."""
    if isinstance(cfg, LatentMoEConfig):
        from .latent_moe import LatentMoETransformer

        return LatentMoETransformer(cfg)
    return Transformer(cfg)


__all__ = ["TransformerConfig", "LatentMoEConfig", "PRESETS", "get_config",
           "Transformer", "build_model"]
