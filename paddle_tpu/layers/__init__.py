from .tensor import *  # noqa: F401,F403
from .nn import *  # noqa: F401,F403
from .io import (data, create_double_buffer_reader,  # noqa: F401
                 create_multi_pass_reader, create_shuffle_reader,
                 open_files, open_recordio_file, read_file)
from . import ops  # noqa: F401  (auto-generated elementwise wrappers)
from .ops import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .learning_rate_scheduler import *  # noqa: F401,F403
from .detection import *  # noqa: F401,F403
from .csp import *  # noqa: F401,F403
from .ssm import *  # noqa: F401,F403
from .cca import *  # noqa: F401,F403
from .delta import *  # noqa: F401,F403
from . import math_op_patch
from .math_op_patch import monkey_patch_variable  # noqa: F401

math_op_patch.monkey_patch_variable()
