"""Operator library (ref: src/operator/ — re-emitted as XLA HLO/Pallas).

Importing this package registers all built-in op families.
"""
from . import registry  # noqa: F401
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import rnn  # noqa: F401
from . import attention  # noqa: F401
from . import moe  # noqa: F401
from . import linear_attention  # noqa: F401
from . import quantization  # noqa: F401
from .registry import get, list_ops, register  # noqa: F401
