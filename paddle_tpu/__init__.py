"""paddle_tpu: a TPU-native deep-learning framework with Paddle-Fluid-era
capabilities, built on JAX/XLA/pjit/Pallas.

The public API mirrors paddle 2.0 (`paddle.*`) plus the fluid static-graph
API (`paddle_tpu.static`, analog of `paddle.fluid`).  See SURVEY.md for the
capability inventory this package implements.
"""
import time as _time

_t_import = _time.perf_counter()     # `import/paddle_tpu` starts here

from .core.dtype import DataType as dtype  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace, XLAPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace,
    set_device, get_device, is_compiled_with_cuda, is_compiled_with_tpu,
    device_count,
)
from .core.program import (  # noqa: F401
    Program, program_guard, default_main_program, default_startup_program,
    name_scope,
)
from .core.generator import seed  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.monitor import stat_add, stat_get, all_stats  # noqa: F401

# kernel library registers all ops on import
from .ops import kernels as _kernels  # noqa: F401

__version__ = "0.1.0"


def _setup_api():
    """Populate the 2.0-style public namespace lazily as subpackages land."""
    import importlib
    for mod in ("dygraph", "tensor", "nn", "optimizer", "static",
                "distributed", "amp", "metric", "io", "vision", "text",
                "hapi", "jit", "incubate", "profiler", "utils", "slim",
                "reader", "dataset", "fluid", "regularizer",
                "distribution", "compat", "sysconfig", "framework",
                "serving", "checkpoint", "observability"):
        try:
            importlib.import_module(f".{mod}", __name__)
        except ImportError:
            continue


_setup_api()

# promote common symbols
from .dygraph.base import (  # noqa: F401
    enable_static, disable_static, in_dynamic_mode, in_dygraph_mode, no_grad,
    set_grad_enabled, is_grad_enabled,
)
from .dygraph.tensor import Tensor, to_tensor  # noqa: F401
from .dygraph.engine import grad  # noqa: F401
from .dygraph.layers import ParamBase  # noqa: F401

try:
    from .tensor import *  # noqa: F401,F403
except ImportError:
    pass
try:
    from .hapi.model import Model, Input  # noqa: F401
except ImportError:
    pass
try:
    from .io.framework_io import save, load  # noqa: F401
except ImportError:
    pass
from .batch import batch  # noqa: F401

# -- 2.0-alpha top-level surface (reference python/paddle/__init__.py) ------
from .tensor.compat import *  # noqa: F401,F403
from .core.dtype import get_default_dtype, set_default_dtype  # noqa: F401
from .core.generator import seed as manual_seed  # noqa: F401
from .core.program import VarDesc as Variable  # noqa: F401
from .static.param_attr import ParamAttr  # noqa: F401
from .optimizer.lr_scheduler import (  # noqa: F401
    NoamDecay, PiecewiseDecay, NaturalExpDecay, ExponentialDecay,
    InverseTimeDecay, PolynomialDecay, CosineDecay,
)
from .distributed.parallel import DataParallel  # noqa: F401
from .core.place import XLAPlace as XPUPlace  # noqa: F401

# LoD containers: ragged sequences are padded+lengths here (io/bucketing
# is the documented redesign); the NAMES alias the eager tensor / a list
# so isinstance checks in ported code keep working.
LoDTensor = Tensor
LoDTensorArray = list


try:
    from .jit import SaveLoadConfig  # noqa: F401
except ImportError:  # jit is in _setup_api's tolerant list
    pass


def get_cuda_rng_state():
    """Parity shim: the RNG is the stateless fold_in generator
    (core/generator.py); returns its seed state."""
    from .core.generator import global_seed
    return [global_seed()]


def set_cuda_rng_state(state):
    from .core.generator import seed as _set_seed
    if state:
        _set_seed(int(state[0]))


# the import as a kept phase, first line to last (kernel registry,
# `_setup_api`'s subpackages): docs/observability.md §6
from .profiler import record_phase as _record_phase  # noqa: E402
_record_phase("import/paddle_tpu", _t_import, _time.perf_counter())
