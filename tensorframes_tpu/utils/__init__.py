from .backend import is_tpu_backend
from .logging import clear_level, get_logger, set_level

__all__ = ["clear_level", "get_logger", "is_tpu_backend", "set_level"]
