"""Operator library.

Importing this package registers every operator family (the equivalent
of the static registration blocks in the reference's ``src/operator/``).
"""

from . import registry
from .registry import OpContext, OpDef, get_op, invoke, list_ops, register

# register all operator families
from . import elemwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import init_ops  # noqa: F401
from . import indexing  # noqa: F401
from . import sample  # noqa: F401
from . import ordering  # noqa: F401
from . import nn  # noqa: F401
from . import rnn  # noqa: F401
from . import custom  # noqa: F401
from . import detection  # noqa: F401
from . import spatial  # noqa: F401
from . import optimizer_op  # noqa: F401
from . import attention  # noqa: F401
from . import adapter  # noqa: F401
from . import hybrid  # noqa: F401

__all__ = ["OpContext", "OpDef", "get_op", "invoke", "list_ops", "register"]
