"""Operator library. Importing this package registers all ops
(analog of the reference's static NNVM_REGISTER_OP registration)."""
from . import registry
from . import elemwise
from . import reduce
from . import matrix
from . import indexing
from . import nn
from . import random_ops
from . import rnn
from . import optimizer_ops
from . import loss_output
from . import attention
from . import indexer
from . import gated_conv
from . import ssd
from . import delta_rule
from . import moe
from . import linalg
from . import contrib_ops
from . import ctc
from . import quantization

from .registry import apply_op, get_op, list_ops, register, Op

__all__ = ["apply_op", "get_op", "list_ops", "register", "Op"]
