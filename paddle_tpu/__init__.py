"""paddle_tpu: a TPU-native deep learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas.

Public surface mirrors ``import paddle`` (reference:
/root/reference/python/paddle/__init__.py): eager Tensors with autograd,
nn.Layer modules, optimizers, AMP, DataLoader, distributed parallelism, jit
capture — re-architected TPU-first (see SURVEY.md §7).
"""
from .framework.dtype import (  # noqa: F401
    bool_ as bool, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    DType as dtype, get_default_dtype, set_default_dtype)
from .framework import (  # noqa: F401
    Tensor, no_grad, enable_grad, set_grad_enabled, seed,
    get_rng_state, set_rng_state, in_dynamic_mode, in_pir_mode)
from .framework.flags import get_flags, set_flags  # noqa: F401
from .framework.tensor import Parameter  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import creation as _creation  # noqa: F401
from .device import (  # noqa: F401
    set_device, get_device, is_compiled_with_cuda, CPUPlace, CUDAPlace,
    CUDAPinnedPlace, TPUPlace)
from . import device  # noqa: F401
from . import autograd  # noqa: F401
from .autograd import grad  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import vision  # noqa: F401
from . import metric  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401
from . import resilience  # noqa: F401
from . import distribution  # noqa: F401
from . import incubate  # noqa: F401
from . import distributed  # noqa: F401
from . import static  # noqa: F401
from . import cost_model  # noqa: F401
from . import decomposition  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import text  # noqa: F401
from . import inference  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import linalg  # noqa: F401
from . import utils  # noqa: F401
from . import hub  # noqa: F401
from . import regularizer  # noqa: F401
from . import onnx  # noqa: F401
from . import sysconfig  # noqa: F401
from . import callbacks  # noqa: F401
from .regularizer import L1Decay, L2Decay  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .framework.io import save, load  # noqa: F401
from .tensor_module import tensor  # noqa: F401
from .nn.layer_base import ParamAttr  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .static.graph import create_parameter  # noqa: F401

def disable_static(place=None):
    from .static.graph import disable_static_mode
    disable_static_mode()
    return None


def enable_static():
    from .static.graph import enable_static_mode
    enable_static_mode()


def in_dygraph_mode():
    return in_dynamic_mode()


def is_grad_enabled():
    from .framework.tensor import grad_enabled
    return grad_enabled()


def disable_signal_handler():
    return None


__version__ = "0.1.0"
