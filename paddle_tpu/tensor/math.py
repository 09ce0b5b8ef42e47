"""Tensor math ops (parity surface: upstream python/paddle/tensor/math.py).

Paddle calling conventions (``x``/``y``, ``axis``, ``keepdim``) over jnp.
XLA fuses these elementwise chains into surrounding matmuls — no hand-fused
kernels needed at this layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    # binary
    "add", "subtract", "multiply", "divide", "floor_divide", "mod",
    "remainder", "pow", "maximum", "minimum", "fmax", "fmin", "atan2",
    "heaviside", "lerp", "outer", "inner", "cross", "dot", "matmul", "mm",
    "bmm", "mv", "add_n", "einsum",
    # unary
    "exp", "expm1", "log", "log2", "log10", "log1p", "sqrt", "rsqrt",
    "square", "reciprocal", "abs", "neg", "sign", "floor", "ceil", "round",
    "trunc", "frac", "sin", "cos", "tan", "asin", "acos", "atan", "sinh",
    "cosh", "asinh", "acosh", "atanh", "erf", "erfinv", "sigmoid", "tanh",
    "deg2rad", "rad2deg", "angle", "conj", "real", "imag", "digamma",
    "lgamma", "logit", "nan_to_num",
    # clip / reductions
    "clip", "sum", "nansum", "mean", "nanmean", "prod", "max", "min",
    "amax", "amin", "cumsum", "cumprod", "cummax", "cummin", "logsumexp",
    "logcumsumexp", "count_nonzero", "all", "any", "diff", "trace",
    "stanh", "trapezoid", "vander",
    # breadth (round 4): the rest of the documented paddle math surface
    "addmm", "bincount", "cdist", "combinations", "copysign",
    "cumulative_trapezoid", "diag_embed", "diagonal", "frexp", "gammainc",
    "gammaincc", "gammaln", "gcd", "hypot", "i0", "i0e", "i1", "i1e",
    "index_add", "index_fill", "index_put", "kron", "lcm", "ldexp",
    "logaddexp", "multigammaln", "nextafter", "polygamma", "renorm", "sgn",
    "sinc", "take", "tensordot",
]


# -- binary ------------------------------------------------------------------

def add(x, y):
    return jnp.add(x, y)


def subtract(x, y):
    return jnp.subtract(x, y)


def multiply(x, y):
    return jnp.multiply(x, y)


def divide(x, y):
    return jnp.divide(x, y)


def floor_divide(x, y):
    return jnp.floor_divide(x, y)


def mod(x, y):
    return jnp.mod(x, y)


remainder = mod


def pow(x, y):
    return jnp.power(x, y)


def maximum(x, y):
    return jnp.maximum(x, y)


def minimum(x, y):
    return jnp.minimum(x, y)


def fmax(x, y):
    return jnp.fmax(x, y)


def fmin(x, y):
    return jnp.fmin(x, y)


def atan2(x, y):
    return jnp.arctan2(x, y)


def heaviside(x, y):
    return jnp.heaviside(x, y)


def lerp(x, y, weight):
    return x + weight * (y - x)


def outer(x, y):
    return jnp.outer(x, y)


def inner(x, y):
    return jnp.inner(x, y)


def cross(x, y, axis=-1):
    return jnp.cross(x, y, axis=axis)


def dot(x, y):
    """paddle.dot: 1-D (or batched row-wise) inner product."""
    return jnp.sum(x * y, axis=-1)


def matmul(x, y, transpose_x: bool = False, transpose_y: bool = False):
    """AMP-aware matmul: under an ``amp.auto_cast`` O1 policy the operands
    are cast to the policy dtype (the reference's white-list dispatch in
    eager amp_utils; models route their projections through here so O1 is
    real, not decorative)."""
    from .. import amp as _amp
    x, y = _amp.cast_inputs("matmul", x, y)
    if transpose_x:
        x = jnp.swapaxes(x, -1, -2)
    if transpose_y:
        y = jnp.swapaxes(y, -1, -2)
    return jnp.matmul(x, y)


def einsum(equation, *operands):
    """AMP-aware einsum (white-listed: it is the MoE dispatch/combine and
    attention workhorse)."""
    from .. import amp as _amp
    operands = _amp.cast_inputs("einsum", *operands)
    return jnp.einsum(equation, *operands)


def mm(x, y):
    return jnp.matmul(x, y)


def bmm(x, y):
    return jnp.matmul(x, y)


def mv(x, vec):
    return jnp.matmul(x, vec)


def add_n(inputs):
    out = inputs[0]
    for t in inputs[1:]:
        out = out + t
    return out


# -- unary -------------------------------------------------------------------

def exp(x):
    return jnp.exp(x)


def expm1(x):
    return jnp.expm1(x)


def log(x):
    return jnp.log(x)


def log2(x):
    return jnp.log2(x)


def log10(x):
    return jnp.log10(x)


def log1p(x):
    return jnp.log1p(x)


def sqrt(x):
    return jnp.sqrt(x)


def rsqrt(x):
    return jax.lax.rsqrt(x)


def square(x):
    return jnp.square(x)


def reciprocal(x):
    return jnp.reciprocal(x)


def abs(x):
    return jnp.abs(x)


def neg(x):
    return jnp.negative(x)


def sign(x):
    return jnp.sign(x)


def floor(x):
    return jnp.floor(x)


def ceil(x):
    return jnp.ceil(x)


def round(x):
    return jnp.round(x)


def trunc(x):
    return jnp.trunc(x)


def frac(x):
    return x - jnp.trunc(x)


def sin(x):
    return jnp.sin(x)


def cos(x):
    return jnp.cos(x)


def tan(x):
    return jnp.tan(x)


def asin(x):
    return jnp.arcsin(x)


def acos(x):
    return jnp.arccos(x)


def atan(x):
    return jnp.arctan(x)


def sinh(x):
    return jnp.sinh(x)


def cosh(x):
    return jnp.cosh(x)


def asinh(x):
    return jnp.arcsinh(x)


def acosh(x):
    return jnp.arccosh(x)


def atanh(x):
    return jnp.arctanh(x)


def erf(x):
    return jax.scipy.special.erf(x)


def erfinv(x):
    return jax.scipy.special.erfinv(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def deg2rad(x):
    return jnp.deg2rad(x)


def rad2deg(x):
    return jnp.rad2deg(x)


def angle(x):
    return jnp.angle(x)


def conj(x):
    return jnp.conj(x)


def real(x):
    return jnp.real(x)


def imag(x):
    return jnp.imag(x)


def digamma(x):
    return jax.scipy.special.digamma(x)


def lgamma(x):
    return jax.scipy.special.gammaln(x)


def logit(x, eps=None):
    if eps is not None:
        x = jnp.clip(x, eps, 1.0 - eps)
    return jnp.log(x / (1.0 - x))


def nan_to_num(x, nan=0.0, posinf=None, neginf=None):
    return jnp.nan_to_num(x, nan=nan, posinf=posinf, neginf=neginf)


# -- clip / reductions -------------------------------------------------------

def clip(x, min=None, max=None):
    return jnp.clip(x, min, max)


def sum(x, axis=None, dtype=None, keepdim: bool = False):
    return jnp.sum(x, axis=axis, dtype=dtype, keepdims=keepdim)


def nansum(x, axis=None, dtype=None, keepdim: bool = False):
    return jnp.nansum(x, axis=axis, dtype=dtype, keepdims=keepdim)


def mean(x, axis=None, keepdim: bool = False):
    return jnp.mean(x, axis=axis, keepdims=keepdim)


def nanmean(x, axis=None, keepdim: bool = False):
    return jnp.nanmean(x, axis=axis, keepdims=keepdim)


def prod(x, axis=None, keepdim: bool = False, dtype=None):
    return jnp.prod(x, axis=axis, dtype=dtype, keepdims=keepdim)


def max(x, axis=None, keepdim: bool = False):
    return jnp.max(x, axis=axis, keepdims=keepdim)


def min(x, axis=None, keepdim: bool = False):
    return jnp.min(x, axis=axis, keepdims=keepdim)


amax = max
amin = min


def cumsum(x, axis=None, dtype=None):
    if axis is None:
        x = jnp.ravel(x)
        axis = 0
    return jnp.cumsum(x, axis=axis, dtype=dtype)


def cumprod(x, dim=None, dtype=None):
    if dim is None:
        x = jnp.ravel(x)
        dim = 0
    return jnp.cumprod(x, axis=dim, dtype=dtype)


def cummax(x, axis=None):
    if axis is None:
        x = jnp.ravel(x)
        axis = 0
    values = jax.lax.associative_scan(jnp.maximum, x, axis=axis)
    # index of the running max = first position attaining the running value
    eq = jnp.equal(jnp.moveaxis(values, axis, -1)[..., :, None],
                   jnp.moveaxis(x, axis, -1)[..., None, :])
    n = x.shape[axis]
    causal = jnp.tril(jnp.ones((n, n), bool))
    idx = jnp.argmax(eq & causal, axis=-1)
    indices = jnp.moveaxis(idx, -1, axis)
    return values, indices


def cummin(x, axis=None):
    values, indices = cummax(-x, axis=axis)
    return -values, indices


def logsumexp(x, axis=None, keepdim: bool = False):
    return jax.scipy.special.logsumexp(x, axis=axis, keepdims=keepdim)


def logcumsumexp(x, axis=None):
    if axis is None:
        x = jnp.ravel(x)
        axis = 0
    # logaddexp is associative → a single XLA scan, numerically stable
    return jax.lax.associative_scan(jnp.logaddexp, x, axis=axis)


def count_nonzero(x, axis=None, keepdim: bool = False):
    return jnp.count_nonzero(x, axis=axis, keepdims=keepdim)


def all(x, axis=None, keepdim: bool = False):
    return jnp.all(x, axis=axis, keepdims=keepdim)


def any(x, axis=None, keepdim: bool = False):
    return jnp.any(x, axis=axis, keepdims=keepdim)


def diff(x, n: int = 1, axis: int = -1):
    return jnp.diff(x, n=n, axis=axis)


def trace(x, offset: int = 0, axis1: int = 0, axis2: int = 1):
    return jnp.trace(x, offset=offset, axis1=axis1, axis2=axis2)


def stanh(x, scale_a: float = 0.67, scale_b: float = 1.7159):
    return scale_b * jnp.tanh(scale_a * x)


def trapezoid(y, x=None, dx=None, axis: int = -1):
    if x is not None and dx is not None:
        raise ValueError("pass either x or dx, not both")
    y = jnp.asarray(y)
    y0 = jnp.take(y, jnp.arange(y.shape[axis] - 1), axis=axis)
    y1 = jnp.take(y, jnp.arange(1, y.shape[axis]), axis=axis)
    if x is not None:
        x = jnp.asarray(x)
        if x.ndim == 1:
            shape = [1] * y.ndim
            shape[axis] = x.shape[0]
            x = x.reshape(shape)
        d = (jnp.take(x, jnp.arange(1, x.shape[axis]), axis=axis)
             - jnp.take(x, jnp.arange(x.shape[axis] - 1), axis=axis))
    else:
        d = 1.0 if dx is None else dx
    return (0.5 * d * (y0 + y1)).sum(axis=axis)


def vander(x, n=None, increasing: bool = False):
    n = x.shape[0] if n is None else n
    powers = jnp.arange(n) if increasing else jnp.arange(n - 1, -1, -1)
    return x[:, None] ** powers[None, :]


# -- breadth (round 4): remaining documented math surface --------------------
# (upstream python/paddle/tensor/math.py; jnp/lax give the math directly,
# the work here is paddle's calling conventions.)

def addmm(input, x, y, beta: float = 1.0, alpha: float = 1.0):
    return beta * input + alpha * jnp.matmul(x, y)


def bincount(x, weights=None, minlength: int = 0):
    # jnp.bincount needs a static length; paddle's output length is
    # max(minlength, max(x)+1), resolved eagerly (host sync).  Inside jit
    # the max is a tracer, so minlength alone sizes the output — pass a
    # large-enough minlength there (values above it are DROPPED by the
    # static-shape clip, the documented jit caveat).
    import jax.core as _core
    length = minlength
    if not isinstance(x, _core.Tracer):
        m = int(jnp.max(x)) + 1 if x.size else 0
        length = m if m > minlength else minlength   # builtin max is shadowed
    return jnp.bincount(x, weights=weights, minlength=length,
                        length=length)


def cdist(x, y, p: float = 2.0):
    diff = x[..., :, None, :] - y[..., None, :, :]
    if p == 2.0:
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    if p == float("inf"):
        return jnp.max(jnp.abs(diff), axis=-1)
    if p == 0.0:
        return jnp.sum((diff != 0).astype(x.dtype), axis=-1)
    return jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)


def combinations(x, r: int = 2, with_replacement: bool = False):
    import itertools
    n = x.shape[0]
    gen = (itertools.combinations_with_replacement(range(n), r)
           if with_replacement else itertools.combinations(range(n), r))
    idx = jnp.asarray(list(gen), dtype=jnp.int32).reshape(-1, r)
    return x[idx]


def copysign(x, y):
    return jnp.copysign(x, y)


def cumulative_trapezoid(y, x=None, dx=None, axis: int = -1):
    y = jnp.asarray(y)
    y0 = jnp.take(y, jnp.arange(y.shape[axis] - 1), axis=axis)
    y1 = jnp.take(y, jnp.arange(1, y.shape[axis]), axis=axis)
    if x is not None:
        x = jnp.asarray(x)
        if x.ndim == 1:
            shape = [1] * y.ndim
            shape[axis] = x.shape[0]
            x = x.reshape(shape)
        d = (jnp.take(x, jnp.arange(1, x.shape[axis]), axis=axis)
             - jnp.take(x, jnp.arange(x.shape[axis] - 1), axis=axis))
    else:
        d = 1.0 if dx is None else dx
    return jnp.cumsum(0.5 * d * (y0 + y1), axis=axis)


def diag_embed(x, offset: int = 0, dim1: int = -2, dim2: int = -1):
    n = x.shape[-1] + (offset if offset >= 0 else -offset)
    k = x.shape[-1]
    out = jnp.zeros(x.shape[:-1] + (n, n), dtype=x.dtype)
    rows = jnp.arange(k) + (0 if offset >= 0 else -offset)
    cols = jnp.arange(k) + (offset if offset >= 0 else 0)
    out = out.at[..., rows, cols].set(x)
    # move the two new axes to dim1/dim2
    nd = out.ndim
    dim1 = dim1 % nd
    dim2 = dim2 % nd
    if (dim1, dim2) != (nd - 2, nd - 1):
        out = jnp.moveaxis(out, (nd - 2, nd - 1), (dim1, dim2))
    return out


def diagonal(x, offset: int = 0, axis1: int = 0, axis2: int = 1):
    return jnp.diagonal(x, offset=offset, axis1=axis1, axis2=axis2)


def frexp(x):
    return jnp.frexp(x)


def gammainc(x, y):
    return jax.scipy.special.gammainc(x, y)


def gammaincc(x, y):
    return jax.scipy.special.gammaincc(x, y)


def gammaln(x):
    return jax.scipy.special.gammaln(x)


def gcd(x, y):
    return jnp.gcd(x, y)


def hypot(x, y):
    return jnp.hypot(x, y)


def i0(x):
    return jax.scipy.special.i0(x)


def i0e(x):
    return jax.scipy.special.i0e(x)


def i1(x):
    return jax.scipy.special.i1(x)


def i1e(x):
    return jax.scipy.special.i1e(x)


def index_add(x, index, axis, value):
    idx = [slice(None)] * x.ndim
    idx[axis] = index
    return x.at[tuple(idx)].add(value)


def index_fill(x, index, axis, value):
    idx = [slice(None)] * x.ndim
    idx[axis] = index
    return x.at[tuple(idx)].set(value)


def index_put(x, indices, value, accumulate: bool = False):
    indices = tuple(indices)
    return (x.at[indices].add(value) if accumulate
            else x.at[indices].set(value))


def kron(x, y):
    return jnp.kron(x, y)


def lcm(x, y):
    return jnp.lcm(x, y)


def ldexp(x, y):
    return jnp.ldexp(x, y)


def logaddexp(x, y):
    return jnp.logaddexp(x, y)


def multigammaln(x, p: int):
    return jax.scipy.special.multigammaln(x, p)


def nextafter(x, y):
    return jnp.nextafter(x, y)


def polygamma(x, n: int):
    # paddle's argument order is (x, n); jax's is (n, x)
    return jax.scipy.special.polygamma(n, x)


def renorm(x, p: float, axis: int, max_norm: float):
    moved = jnp.moveaxis(x, axis, 0)
    flat = moved.reshape(moved.shape[0], -1)
    norms = jnp.sum(jnp.abs(flat) ** p, axis=1) ** (1.0 / p)
    scale = jnp.where(norms > max_norm, max_norm / (norms + 1e-7), 1.0)
    out = flat * scale[:, None]
    return jnp.moveaxis(out.reshape(moved.shape), 0, axis)


def sgn(x):
    if jnp.iscomplexobj(x):
        mag = jnp.abs(x)
        return jnp.where(mag == 0, 0, x / jnp.where(mag == 0, 1.0, mag))
    return jnp.sign(x)


def sinc(x):
    return jnp.sinc(x)


def take(x, index, mode: str = "raise"):
    flat = jnp.ravel(x)
    index = jnp.asarray(index)
    if mode == "wrap":
        index = jnp.mod(index, flat.shape[0])
    else:  # 'raise' can't raise inside jit; clip matches XLA gather semantics
        index = jnp.clip(index, -flat.shape[0], flat.shape[0] - 1)
    return flat[index]


def tensordot(x, y, axes=2):
    if isinstance(axes, (list, tuple)):
        axes = tuple(tuple(a) if isinstance(a, (list, tuple)) else a
                     for a in axes)
    return jnp.tensordot(x, y, axes=axes)
