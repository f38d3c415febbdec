"""Utilities (reference: ``utils/``)."""

from . import batch_utils
from . import logger
from . import tensor_capture
from .logger import get_logger, rmsg

__all__ = ["batch_utils", "logger", "tensor_capture", "get_logger", "rmsg"]
