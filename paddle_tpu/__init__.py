"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Built from scratch for JAX/XLA/Pallas/pjit — not a port. See SURVEY.md at the repo root for the
reference blueprint this build follows; reference file:line citations appear in module docstrings.
"""
from __future__ import annotations

import time as _time

_import_t0 = _time.perf_counter()      # the span `startup.import` starts here

from .version import full_version as __version__

# int64 is paddle's default integer dtype; jax demotes to 32-bit unless x64 is on.
# Float defaults remain f32 because every creation path passes dtype explicitly
# (python float scalars stay weakly typed, so f64 does not leak into f32 compute).
import jax as _jax

_jax.config.update("jax_enable_x64", True)

# ---- core ----
from .core import dtype as _dtype_mod
from .core.dtype import (
    bfloat16, bool_, complex64, complex128, convert_dtype, finfo, float16,
    float32, float64, get_default_dtype, iinfo, int8, int16, int32, int64,
    set_default_dtype, uint8,
)
from .core.place import (
    CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, IPUPlace, MLUPlace,
    NPUPlace, NPUPinnedPlace, Place, TPUPlace, XPUPlace, device_count,
    get_device, is_compiled_with_cinn, is_compiled_with_cuda,
    is_compiled_with_distribute, is_compiled_with_ipu, is_compiled_with_mlu,
    is_compiled_with_npu, is_compiled_with_rocm, is_compiled_with_tpu,
    is_compiled_with_xpu, set_device,
)
from .core.random import get_rng_state, seed, set_rng_state

# the reference's CUDA RNG state API maps onto the single device RNG here
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
from .core.flags import get_flags, set_flags
from .core import compile_cache as _compile_cache  # noqa: F401  (places
#   the persistent compile cache at import, before the first compile)
from .core.tensor import Tensor
from .core.autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled
from .core.dispatch import amp_guard as _amp_guard  # noqa: F401

# ---- ops (also attaches Tensor methods) ----
from .ops import *  # noqa: F401,F403
from .ops import F as _F  # noqa: F401

bool = bool_  # paddle.bool

# ---- subpackages ----
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import autograd  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import vision  # noqa: E402
from . import distributed  # noqa: E402
from . import jit  # noqa: E402
from . import static  # noqa: E402
from . import inference  # noqa: E402
from . import serving  # noqa: E402
from . import fft  # noqa: E402
from .ops import linalg as linalg  # noqa: E402
import sys as _sys
_sys.modules[__name__ + ".linalg"] = linalg  # importable paddle_tpu.linalg, like paddle.linalg
del _sys
from . import distribution  # noqa: E402
from . import sparse  # noqa: E402
from . import strings  # noqa: E402
from . import text  # noqa: E402
from . import incubate  # noqa: E402
from . import metric  # noqa: E402
from . import observability  # noqa: E402
from . import profiler  # noqa: E402
from . import device  # noqa: E402
from . import utils  # noqa: E402
from . import regularizer  # noqa: E402
from . import signal  # noqa: E402
from . import callbacks  # noqa: E402
from . import hub  # noqa: E402
from . import sysconfig  # noqa: E402
from . import reader  # noqa: E402
from . import onnx  # noqa: E402
from . import compat  # noqa: E402
from . import cost_model  # noqa: E402
from . import hapi  # noqa: E402
from .hapi import Model  # noqa: E402
from .framework import io as _fw_io  # noqa: E402
from .framework.io import load, save  # noqa: E402
from .jit import to_static  # noqa: E402

# paddle.disable_static / enable_static parity: dygraph is the default mode.
_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def disable_static(place=None):
    global _static_mode
    _static_mode = False
    if place is not None:
        set_device(place)


def in_dynamic_mode():
    return not _static_mode


def is_grad_enabled_():  # legacy alias
    return is_grad_enabled()


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary

    return _summary(net, input_size, dtypes=dtypes, input=input)


def flops(net, input_size=None, inputs=None, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops

    return _flops(net, input_size, inputs=inputs, custom_ops=custom_ops,
                  print_detail=print_detail)


# ---- remaining top-level parity surface ----
from .nn.layer import ParamAttr, create_parameter  # noqa: E402
from .distributed.meta_parallel.data_parallel import DataParallel  # noqa: E402

import numpy as _np  # noqa: E402

dtype = _np.dtype  # paddle.dtype: dtypes here ARE numpy dtypes (see core/dtype.py)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    from .core import tensor as _tensor_mod

    opts = _tensor_mod._print_options
    if precision is not None:
        opts["precision"] = precision
    if threshold is not None:
        opts["threshold"] = threshold
    if edgeitems is not None:
        opts["edgeitems"] = edgeitems
    if linewidth is not None:
        opts["max_line_width"] = linewidth
    if sci_mode is not None:
        opts["suppress_small"] = not sci_mode


def disable_signal_handler():
    """No-op: unlike the reference (platform/init.cc SignalHandle) no custom
    signal handlers are installed, so there is nothing to disable."""


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batch reader (reference: python/paddle/batch.py)."""

    def batch_reader():
        b = []
        for item in reader():
            b.append(item)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader


def tolist(x):
    return x.tolist()


def tanh_(x):
    return x.tanh_()


def squeeze_(x, axis=None, name=None):
    return x.squeeze_(axis)


def unsqueeze_(x, axis, name=None):
    return x.unsqueeze_(axis)


def scatter_(x, index, updates, overwrite=True, name=None):
    return x.scatter_(index, updates, overwrite)


from .observability import tracer as _tracer  # noqa: E402

_tracer.get_tracer().record_complete(
    "startup.import", _import_t0, _time.perf_counter(),
    span_id=_tracer.new_span_id(), always=True)
del _time, _import_t0, _tracer
