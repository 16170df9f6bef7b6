from repro.models.config import ModelConfig, ShapeConfig
from repro.models import transformer, femnist_cnn


def init_params(cfg, key=None, abstract=False, tp: int = 16):
    if cfg.family == "cnn":
        return femnist_cnn.init_params(cfg, key, abstract, tp)
    return transformer.init_params(cfg, key, abstract, tp)


def loss_fn(params, batch, cfg, rules):
    if cfg.family == "cnn":
        return femnist_cnn.loss_fn(params, batch, cfg, rules)
    return transformer.loss_fn(params, batch, cfg, rules)


__all__ = [
    "ModelConfig", "ShapeConfig", "init_params", "loss_fn", "transformer",
    "femnist_cnn",
]
