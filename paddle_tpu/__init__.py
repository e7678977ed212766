"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Built from scratch on JAX/XLA/PJRT idioms (see SURVEY.md for the reference map):
- eager tensors are jax.Arrays in HBM; every op is a cached XLA computation
- autograd is a Python tape over jax.vjp pullbacks (fluid/eager analog)
- graph capture (`jit.to_static`) compiles whole training steps with jax.jit
- parallelism is mesh/GSPMD-first: shard_tensor/reshard + fleet hybrid-parallel wrappers
"""
from __future__ import annotations

import os as _os

import jax as _jax

# float64/int64 support (paddle has first-class fp64); default creation dtype stays fp32.
_jax.config.update("jax_enable_x64", True)

# Explicit platform override (e.g. PADDLE_TPU_PLATFORM=cpu), applied before any
# backend starts; the same as setting JAX_PLATFORMS.
if _os.environ.get("PADDLE_TPU_PLATFORM"):
    _jax.config.update("jax_platforms", _os.environ["PADDLE_TPU_PLATFORM"])

# Multi-process bootstrap MUST precede any XLA backend touch (jax.distributed's
# contract), and importing the op library below initializes the backend — so when
# the launcher's env contract marks a multi-process run, rendezvous now.
from ._bootstrap import early_init_distributed as _early_init  # noqa: E402

_early_init()  # no-op unless the env marks a multi-process run
del _early_init

from .framework import dtype as _dtype_mod  # noqa: E402
from .framework.dtype import (  # noqa: F401,E402
    bfloat16, bool_, complex64, complex128, float16, float32, float64, get_default_dtype,
    int8, int16, int32, int64, set_default_dtype, uint8,
)
from .framework.core import Parameter, Tensor, to_tensor  # noqa: F401,E402
from .framework.flags import get_flags, set_flags  # noqa: F401,E402
from .framework import random as _random  # noqa: E402
from .framework.random import get_rng_state, set_rng_state  # noqa: F401,E402

bool = bool_  # noqa: A001  (reference exports the dtype as paddle.bool)
dtype = _dtype_mod.convert_dtype  # dtype constructor (paddle.dtype('float32'))
# CUDA rng-state APIs map onto the single global threefry state
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
try:  # fp8 dtypes exist on current jax; keep optional
    from jax.numpy import float8_e4m3fn, float8_e5m2  # noqa: F401,E402
except ImportError:
    pass
from .autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401,E402
from .ops import *  # noqa: F401,F403,E402
from .ops import (  # noqa: F401,E402  (names shadowed by python builtins in *)
    abs, all, any, max, min, pow, round, slice, sum, complex,
)

from . import amp  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import framework  # noqa: F401,E402
from . import linalg  # noqa: F401,E402

# `from .ops import *` bound `linalg` to the ops submodule first, which makes
# the from-import above a no-op (the parent attr already exists) — import the
# public module explicitly and force it to win
import importlib as _importlib  # noqa: E402

linalg = _importlib.import_module("paddle_tpu.linalg")
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import ops  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import monitor  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import dataset  # noqa: F401,E402

# vision/hapi/models import lazily-heavy deps; exposed as regular submodules
from . import vision  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from . import models  # noqa: F401,E402
from .hapi import Model, summary  # noqa: F401,E402

# round-3 export-surface sweep: these reference namespaces must exist on BARE
# import (the round-2 probe found paddle.profiler absent until explicitly
# imported; python/paddle/__init__.py exports all of these)
from . import base  # noqa: F401,E402
from . import version  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import ops as tensor  # noqa: F401,E402  (paddle.tensor == the op surface)
from . import _C_ops  # noqa: F401,E402  (generated-op-module compat; lazy resolution)
from . import _legacy_C_ops  # noqa: F401,E402
from . import cost_model  # noqa: F401,E402
import sys as _sys  # noqa: E402

# submodule-import syntax ("import paddle.tensor", "from paddle.tensor import
# x") needs a sys.modules entry, not just the attribute alias
_sys.modules[__name__ + ".tensor"] = tensor
from .tensor_array import (  # noqa: F401,E402
    array_length, array_read, array_write, create_array,
)


def seed(s):
    """paddle.seed: reseed the global generator."""
    return _random.seed(s)


def rank(x):
    return x.ndim


def shape(x):
    from .ops import to_tensor as _tt

    import jax.numpy as jnp

    return Tensor(jnp.asarray(x.value.shape, dtype="int64"))


def save(obj, path, **kwargs):
    from .framework_io import save as _save

    return _save(obj, path, **kwargs)


def load(path, **kwargs):
    from .framework_io import load as _load

    return _load(path, **kwargs)


def set_device(dev):
    return device.set_device(dev)


def get_device():
    return device.get_device()


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(name="tpu"):
    return name == "tpu"


def in_dynamic_mode():
    from .autograd import tape as _tape

    if _STATIC_MODE[0]:
        return False  # reference contract: enable_static() flips this
    return not _tape.in_functional_mode()


_STATIC_MODE = [False]


def disable_static(place=None):
    from .framework import capture as _capture

    _STATIC_MODE[0] = False
    _capture.set_default(None)


def enable_static():
    """Reference static mode: ops dispatched from here on are recorded into
    the default main Program (capture-replay, paddle_tpu/static) so the
    guard-less reference idiom — enable_static + static.data + ops +
    Executor.run — replays against the feed instead of silently returning
    placeholder results. program_guard still scopes recording to an explicit
    Program."""
    from .framework import capture as _capture

    _STATIC_MODE[0] = True
    # the PROCESS-GLOBAL default main program, not default_main_program()
    # (which resolves thread-locally and inside a program_guard would
    # install the transient guarded program as the process-wide default)
    _capture.set_default(static._MAIN[0])


def in_static_mode():
    return _STATIC_MODE[0]


def disable_signal_handler():
    pass


CPUPlace = type("CPUPlace", (), {"__repr__": lambda self: "Place(cpu)"})
TPUPlace = type("TPUPlace", (), {"__repr__": lambda self: "Place(tpu:0)"})
CUDAPlace = TPUPlace  # alias so reference-style code keeps running on TPU
CustomPlace = TPUPlace

__version__ = "0.1.0"
CUDAPinnedPlace = CPUPlace  # pinned host staging == host memory here

from .distributed.parallel import DataParallel  # noqa: F401,E402


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """paddle.create_parameter (tensor/creation.py): a trainable Parameter
    via the same attr/initializer pipeline as Layer.create_parameter."""
    from .nn.layer.layers import Layer

    holder = Layer()
    holder._dtype = dtype
    p = holder.create_parameter(list(shape), attr=attr, dtype=dtype,
                                is_bias=is_bias,
                                default_initializer=default_initializer)
    if name is not None and p is not None:
        p.name = name
    return p


def reduce_as(x, target, name=None):
    """Sum x over leading/broadcast axes until it matches target's shape."""
    from .ops import reduction as _red

    xs, ts = list(x.shape), list(target.shape)
    while len(xs) > len(ts):
        x = _red.sum(x, axis=0)
        xs = list(x.shape)
    axes = [i for i, (a, b) in enumerate(zip(xs, ts)) if a != b and b == 1]
    if axes:
        x = _red.sum(x, axis=axes, keepdim=True)
    return x


def batch(reader, batch_size, drop_last=False):
    """Legacy reader combinator (paddle.batch): groups samples into lists."""

    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


class LazyGuard:
    """paddle.LazyGuard: the reference delays parameter materialization; this
    build initializes eagerly (PJRT buffers are cheap on host), so the guard
    is a transparent context that exists for API compatibility."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Model FLOPs estimate by forward hooks (hapi/dynamic_flops.py)."""
    from .hapi.flops_counter import count_flops

    return count_flops(net, input_size, custom_ops=custom_ops,
                       print_detail=print_detail)

# last reference top-level __all__ stragglers (python/paddle/__init__.py)
from .nn.initializer import ParamAttr  # noqa: F401,E402
from .ops import (  # noqa: F401,E402
    addmm_, index_add_, index_fill_, index_put_, renorm_,
)

# string/raw dtype sentinels (framework/dtype.py pstring/raw; tokenizer and
# extension-op surfaces reference them — see framework/containers.StringTensor)
pstring = "pstring"
raw = "raw"


def check_shape(shape):
    """utils/layers_utils.py:483 check_shape: validate a fill_constant shape
    (same check ORDER as the reference: negative -> ValueError first, then
    non-integer -> TypeError; bool passes as int there and here)."""
    from .framework.core import Tensor as _T

    if isinstance(shape, _T):
        return
    if isinstance(shape, (list, tuple)):
        for ele in shape:
            if isinstance(ele, _T):
                continue
            import numpy as _np

            if ele < 0:
                raise ValueError(
                    "All elements in ``shape`` must be positive when it's "
                    "a list or tuple")
            if not isinstance(ele, (int, _np.integer)):
                raise TypeError(
                    "All elements in ``shape`` must be integers when it's "
                    "a list or tuple")


# graftsan runtime sanitizers (analysis/sanitizers.py): opt-in via
# PADDLE_TPU_SANITIZE=lock,recompile,hostsync — disabled (and costless)
# otherwise. Installed at the END of package init so the lock wrapper sees
# the monitor/trace module globals it swaps.
from .analysis.sanitizers import install_from_env as _san_install  # noqa: E402

_san_install()

# fault-injection harness (analysis/faultinject.py): opt-in via
# PADDLE_TPU_FAULTS=point:action:trigger;... — the offensive twin of the
# sanitizers, arming named chaos-drill points in the serving/KV stack.
from .analysis.faultinject import install_from_env as _fi_install  # noqa: E402

_fi_install()

# graftscope debug endpoint (monitor/server.py): opt-in via
# PADDLE_TPU_DEBUG_PORT=<port> — without it no listening socket and no
# server thread ever exist (the introspection plane's off-cost is zero).
from .monitor.server import install_from_env as _obs_install  # noqa: E402

_obs_install()
