"""Mixture-of-experts (reference: ``modules/moe/``)."""

from . import config_validator
from . import expert_mlps
from . import model
from . import routing
from . import token_shuffling
from .config_validator import validate_moe_config
from .expert_mlps import ExpertMLPs, build_dispatch_combine, compute_capacity
from .model import MoE, SharedExperts
from .routing import (GroupLimitedRouter, RouterSigmoid, RouterSinkhorn,
                      RouterSoftmaxBias, RouterTopK)

__all__ = [
    "config_validator",
    "validate_moe_config",
    "expert_mlps",
    "token_shuffling",
    "model",
    "routing",
    "ExpertMLPs",
    "build_dispatch_combine",
    "compute_capacity",
    "MoE",
    "SharedExperts",
    "GroupLimitedRouter",
    "RouterSinkhorn",
    "RouterSigmoid",
    "RouterSoftmaxBias",
    "RouterTopK",
]
