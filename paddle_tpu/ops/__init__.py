"""Op library. Importing this package registers every op type.

TPU-native replacement for the reference op library
(paddle/fluid/operators/ — ~130 op types, see SURVEY.md N11-N14): each op
is a pure-JAX compute rule traced into the executor's XLA program.
"""
from . import core_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import beam_search_ops  # noqa: F401

from ..core.registry import OpRegistry


def all_ops():
    return OpRegistry.all_ops()
from . import csp_ops  # noqa: F401
from . import reader_ops  # noqa: F401
from . import augment_ops  # noqa: F401
from . import cache_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import cca_ops  # noqa: F401
from . import delta_ops  # noqa: F401
