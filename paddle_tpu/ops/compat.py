"""Top-level surface parity batch: numpy-family helpers, scatter views,
special functions, samplers, and auto-generated inplace variants.

Reference analogs: python/paddle/tensor/{math,manipulation,linalg,random}.py
entries exported from python/paddle/__init__.py that round 1 missed. Each op
is a defop (tape autograd + AMP + capture); the `*_` in-place family is
generated from the out-of-place ops (eager semantics: compute, then rebind
the tensor's buffer — matching the reference's inplace API shape).
"""
from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import random as rng
from ..framework.core import Tensor
from ._apply import defop


# -- stacking / splitting -----------------------------------------------------
def add_n(inputs, name=None):
    out = inputs[0]
    for x in inputs[1:]:
        out = out + x
    return out


def _seq(xs):
    return [x for x in (xs if isinstance(xs, (list, tuple)) else [xs])]


def hstack(x, name=None):
    from .manipulation import concat, stack

    xs = _seq(x)
    if xs[0].ndim == 0:
        return stack(xs)
    axis = 0 if xs[0].ndim == 1 else 1
    return concat(xs, axis=axis)


def vstack(x, name=None):
    from .manipulation import concat, reshape

    xs = [reshape(t, [1, -1]) if t.ndim <= 1 else t for t in _seq(x)]
    return concat(xs, axis=0)


row_stack = vstack


def column_stack(x, name=None):
    from .manipulation import concat, reshape

    xs = [reshape(t, [-1, 1]) if t.ndim <= 1 else t for t in _seq(x)]
    return concat(xs, axis=1)


def dstack(x, name=None):
    from .manipulation import concat, reshape

    out = []
    for t in _seq(x):
        if t.ndim == 1:
            t = reshape(t, [1, -1, 1])
        elif t.ndim == 2:
            t = reshape(t, list(t.shape) + [1])
        out.append(t)
    return concat(out, axis=2)


def hsplit(x, num_or_indices, name=None):
    from .manipulation import tensor_split

    axis = 0 if x.ndim == 1 else 1
    return tensor_split(x, num_or_indices, axis=axis)


def vsplit(x, num_or_indices, name=None):
    from .manipulation import tensor_split

    return tensor_split(x, num_or_indices, axis=0)


def dsplit(x, num_or_indices, name=None):
    from .manipulation import tensor_split

    return tensor_split(x, num_or_indices, axis=2)


@defop("block_diag")
def block_diag(inputs):
    return jax.scipy.linalg.block_diag(
        *[jnp.atleast_2d(x) for x in inputs])


@defop("cartesian_prod")
def cartesian_prod(x):
    grids = jnp.meshgrid(*list(x), indexing="ij")
    return jnp.stack([g.ravel() for g in grids], axis=-1)


def combinations(x, r=2, with_replacement=False, name=None):
    from .manipulation import stack

    n = int(x.shape[0])
    idx = (itertools.combinations_with_replacement(range(n), r)
           if with_replacement else itertools.combinations(range(n), r))
    idx = np.array(list(idx), "int64").reshape(-1, r)
    rows = [x[Tensor(jnp.asarray(idx[:, j]))] for j in range(r)]
    return stack(rows, axis=1)


# -- views / scatters ---------------------------------------------------------
@defop("matrix_transpose")
def matrix_transpose(x):
    return jnp.swapaxes(x, -1, -2)


@defop("diagonal_scatter")
def diagonal_scatter(x, y, offset=0, axis1=0, axis2=1):
    n1, n2 = x.shape[axis1], x.shape[axis2]
    rows = jnp.arange(max(n1, n2))
    r = rows - min(offset, 0) * 0 + (-offset if offset < 0 else 0)
    c = rows + (offset if offset > 0 else 0)
    k = min(n1 - (-offset if offset < 0 else 0),
            n2 - (offset if offset > 0 else 0))
    r, c = r[:k], c[:k]
    moved = jnp.moveaxis(x, (axis1, axis2), (-2, -1))
    moved = moved.at[..., r, c].set(jnp.moveaxis(jnp.asarray(y), -1, -1))
    return jnp.moveaxis(moved, (-2, -1), (axis1, axis2))


@defop("select_scatter")
def select_scatter(x, values, axis, index):
    moved = jnp.moveaxis(x, axis, 0)
    moved = moved.at[index].set(values)
    return jnp.moveaxis(moved, 0, axis)


@defop("slice_scatter")
def slice_scatter(x, value, axes, starts, ends, strides):
    idx = [slice(None)] * x.ndim
    for ax, s, e, st in zip(axes, starts, ends, strides):
        idx[int(ax)] = slice(int(s), int(e), int(st))
    return x.at[tuple(idx)].set(value)


@defop("take")
def take(x, index, mode="raise"):
    flat = x.ravel()
    idx = index.astype(jnp.int64)
    if mode == "wrap":
        idx = jnp.mod(idx, flat.shape[0])
    else:  # raise/clip both clip under jit (no host roundtrip)
        idx = jnp.where(idx < 0, idx + flat.shape[0], idx)
        idx = jnp.clip(idx, 0, flat.shape[0] - 1)
    return flat[idx]


@defop("unflatten")
def unflatten(x, axis, shape):
    axis = axis % x.ndim
    new = list(x.shape[:axis]) + [int(s) for s in shape] \
        + list(x.shape[axis + 1:])
    return x.reshape(new)


@defop("unfold")
def unfold(x, axis, size, step):
    axis = axis % x.ndim
    n = (x.shape[axis] - size) // step + 1
    starts = jnp.arange(n) * step
    moved = jnp.moveaxis(x, axis, 0)
    windows = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(moved, s, size, 0))(starts)
    # (n, size, ...rest) -> original dims with n at `axis`, size appended LAST
    # (reference Tensor.unfold layout, e.g. (4,5).unfold(1,3,2) -> (4,2,3))
    windows = jnp.moveaxis(windows, 1, -1)   # (n, ...rest, size)
    return jnp.moveaxis(windows, 0, axis)


def reverse(x, axis, name=None):
    from .manipulation import flip

    return flip(x, axis)


# -- math ---------------------------------------------------------------------
@defop("tensordot")
def tensordot(x, y, axes=2):
    if isinstance(axes, (list, tuple)):
        axes = tuple(tuple(int(i) for i in a) if isinstance(a, (list, tuple))
                     else int(a) for a in axes)
    return jnp.tensordot(x, y, axes=axes)


@defop("vecdot")
def vecdot(x, y, axis=-1):
    return jnp.sum(x * y, axis=axis)


@defop("cdist")
def cdist(x, y, p=2.0, compute_mode="use_mm_for_euclid_dist_if_necessary"):
    diff = x[..., :, None, :] - y[..., None, :, :]
    if p == 2.0:
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1) + 1e-30)
    return jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)


@defop("pdist")
def pdist(x, p=2.0):
    n = x.shape[0]
    iu, ju = np.triu_indices(n, k=1)
    diff = x[iu] - x[ju]
    if p == 2.0:
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1) + 1e-30)
    return jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)


@defop("sinc")
def sinc(x):
    return jnp.sinc(x)


@defop("sgn")
def sgn(x):
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        mag = jnp.abs(x)
        return jnp.where(mag == 0, 0.0 + 0.0j, x / jnp.where(mag == 0, 1, mag))
    return jnp.sign(x)


@defop("signbit", differentiable=False)
def signbit(x):
    return jnp.signbit(x)


@defop("positive")
def positive(x):
    return +x


@defop("frexp", differentiable=False)
def frexp(x):
    m, e = jnp.frexp(x)
    return m, e.astype(jnp.int32)


@defop("renorm")
def renorm(x, p, axis, max_norm):
    moved = jnp.moveaxis(x, axis, 0)
    flat = moved.reshape(moved.shape[0], -1)
    norms = jnp.sum(jnp.abs(flat) ** p, axis=1) ** (1.0 / p)
    factor = jnp.where(norms > max_norm, max_norm / (norms + 1e-7), 1.0)
    out = flat * factor[:, None]
    return jnp.moveaxis(out.reshape(moved.shape), 0, axis)


@defop("cumulative_trapezoid")
def cumulative_trapezoid(y, x=None, dx=1.0, axis=-1):
    y0 = jnp.moveaxis(y, axis, -1)
    avg = (y0[..., 1:] + y0[..., :-1]) / 2.0
    if x is not None:
        xd = jnp.diff(jnp.moveaxis(jnp.asarray(x), axis, -1)
                      if np.ndim(x) > 1 else jnp.asarray(x), axis=-1)
        seg = avg * xd
    else:
        seg = avg * dx
    return jnp.moveaxis(jnp.cumsum(seg, axis=-1), -1, axis)


@defop("histogram_bin_edges", differentiable=False)
def histogram_bin_edges(x, bins=100, min=0.0, max=0.0):  # noqa: A002
    lo, hi = (jnp.min(x), jnp.max(x)) if min == 0.0 and max == 0.0 \
        else (min, max)
    return jnp.linspace(lo, hi, bins + 1)


@defop("isin", differentiable=False)
def isin(x, test_x, assume_unique=False, invert=False):
    out = jnp.isin(x, test_x)
    return ~out if invert else out


@defop("isneginf", differentiable=False)
def isneginf(x):
    return jnp.isneginf(x)


@defop("isposinf", differentiable=False)
def isposinf(x):
    return jnp.isposinf(x)


@defop("isreal", differentiable=False)
def isreal(x):
    return jnp.isreal(x)


def is_empty(x, name=None):
    return Tensor(jnp.asarray(int(np.prod(x.shape)) == 0))


@defop("as_complex")
def as_complex(x):
    return jax.lax.complex(x[..., 0], x[..., 1])


@defop("as_real")
def as_real(x):
    return jnp.stack([jnp.real(x), jnp.imag(x)], axis=-1)


# -- special functions --------------------------------------------------------
@defop("gammaln")
def gammaln(x):
    return jax.scipy.special.gammaln(x)


@defop("gammainc")
def gammainc(x, y):
    return jax.scipy.special.gammainc(x, y)


@defop("gammaincc")
def gammaincc(x, y):
    return jax.scipy.special.gammaincc(x, y)


@defop("multigammaln")
def multigammaln(x, p):
    j = jnp.arange(1, p + 1, dtype=x.dtype)
    return (p * (p - 1) / 4.0) * jnp.log(jnp.pi) + jnp.sum(
        jax.scipy.special.gammaln(x[..., None] + (1.0 - j) / 2.0), axis=-1)


@defop("polygamma")
def polygamma(x, n):
    return jax.scipy.special.polygamma(n, x)


# -- samplers -----------------------------------------------------------------
def standard_gamma(x, name=None):
    return Tensor(jax.random.gamma(rng.next_key(), x.value)
                  .astype(x.value.dtype))


def binomial(count, prob, name=None):
    c = count.value if isinstance(count, Tensor) else jnp.asarray(count)
    p = prob.value if isinstance(prob, Tensor) else jnp.asarray(prob)
    dt = p.dtype if jnp.issubdtype(p.dtype, jnp.floating) else jnp.float32
    return Tensor(jax.random.binomial(rng.next_key(), c.astype(dt),
                                      p.astype(dt))
                  .astype(jnp.int64))


def log_normal(mean=1.0, std=2.0, shape=None, name=None):
    shape = tuple(shape or [])
    z = jax.random.normal(rng.next_key(), shape)
    return Tensor(jnp.exp(mean + std * z))


# -- misc ---------------------------------------------------------------------
def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr formats through numpy; forward the knobs (tensor/to_string)."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


def tolist(x):
    return x.tolist()


def to_dlpack(x):
    return jax.dlpack.to_dlpack(x.value) if hasattr(
        jax.dlpack, "to_dlpack") else x.value.__dlpack__()


def from_dlpack(capsule):
    return Tensor(jnp.from_dlpack(capsule))


# -- auto-generated inplace variants ------------------------------------------
def _make_inplace(fn):
    def inplace(x, *args, **kwargs):
        out = fn(x, *args, **kwargs)
        out = out[0] if isinstance(out, tuple) else out
        x._replace_value(out.value if isinstance(out, Tensor) else out)
        return x

    inplace.__name__ = fn.__name__ + "_"
    return inplace


_INPLACE_NAMES = [
    "abs", "acos", "atan", "bitwise_and", "bitwise_not", "bitwise_or",
    "bitwise_xor", "bitwise_left_shift", "bitwise_right_shift", "cast",
    "copysign", "cos", "cumprod", "cumsum", "digamma", "equal", "erf",
    "expm1", "flatten", "floor_divide", "floor_mod", "frac", "gcd",
    "greater_equal", "greater_than", "hypot", "i0", "lcm", "ldexp",
    "less_equal", "less_than", "lgamma", "log", "log10", "log2",
    "logical_and", "logical_not", "logical_or", "logit", "masked_fill",
    "masked_scatter", "mod", "nan_to_num", "neg", "pow", "remainder",
    "sin", "sinh", "square", "t", "tan", "tanh", "transpose", "tril",
    "triu", "trunc", "where",
]


def _install_inplace(namespace):
    made = {}
    for name in _INPLACE_NAMES:
        fn = namespace.get(name)
        if callable(fn) and name + "_" not in namespace:
            made[name + "_"] = _make_inplace(fn)
    made.setdefault("gammaln_", _make_inplace(gammaln))
    made.setdefault("gammainc_", _make_inplace(gammainc))
    made.setdefault("gammaincc_", _make_inplace(gammaincc))
    made.setdefault("multigammaln_", _make_inplace(multigammaln))
    made.setdefault("polygamma_", _make_inplace(polygamma))
    made.setdefault("sinc_", _make_inplace(sinc))
    made.setdefault("less_", made.get("less_than_", None) or _make_inplace(
        namespace["less_than"]))
    return made


bitwise_invert = None  # bound in ops/__init__ (alias of bitwise_not)
