"""Tiny-shape smoke invocations for every TARGET_SURFACE op.

The round-3 verdict's core finding: every CI test ran on the fake CPU mesh,
so an op that only breaks on the real chip (``eig``: no TPU lowering) stayed
"implemented" in the registry while crashing in users' hands.  This module
is the antidote — for each name in
:mod:`paddle_tpu.framework.op_registry`'s TARGET_SURFACE it records one
concrete tiny-shape call, so the TPU lane (``PT_TPU_LANE=1 pytest -m tpu``)
can execute the whole surface on-device.  The reference's equivalent is its
per-op OpTest grid running in the GPU CI lane (SURVEY §4 op-unit-tests +
CI-driver rows); numerical semantics are covered by the CPU-lane OpTests —
this sweep only asserts "compiles and executes on the chip".

Shapes are deliberately tiny (≤ 4×4-ish): the point is lowering coverage,
not perf; the bench owns perf.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import op_registry

# ---------------------------------------------------------------------------
# canonical tiny inputs (built lazily so importing this module stays cheap
# and never touches a backend)
# ---------------------------------------------------------------------------


def _inputs() -> Dict[str, Any]:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(2, 3)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(3, 3)) + 3.0 * np.eye(3), jnp.float32)
    spd = m @ m.T + 3.0 * jnp.eye(3)
    tri = jnp.triu(m) + 2.0 * jnp.eye(3)
    v = jnp.asarray([0.3, -1.2, 2.1], jnp.float32)
    vs = jnp.asarray([-2.0, -0.5, 0.5, 2.0], jnp.float32)  # sorted
    unit = jnp.asarray(rng.uniform(0.05, 0.95, size=(2, 3)), jnp.float32)
    pos = jnp.abs(x) + 0.5
    b3 = jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32)
    b3t = jnp.asarray(rng.normal(size=(2, 4, 3)), jnp.float32)
    img = jnp.asarray(rng.normal(size=(1, 4, 4, 4)), jnp.float32)  # NCHW
    ids = jnp.asarray([[1, 4, 2], [0, 3, 5]], jnp.int32)
    iarr = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)  # B,S,H,D
    return dict(x=x, y=y, m=m, spd=spd, tri=tri, v=v, vs=vs, unit=unit,
                pos=pos, b3=b3, b3t=b3t, img=img, ids=ids, iarr=iarr,
                q=q, rng=rng)


# categories whose default pattern is f(x) on the 2×3 float array
_UNARY_DEFAULT = {"paddle.math", "paddle.logic"}
# math/logic ops that take (x, y)
_BINARY = {
    "add", "atan2", "divide", "fmax", "fmin", "heaviside", "maximum",
    "minimum", "multiply", "pow", "subtract",
    "allclose", "equal", "equal_all", "greater_equal", "greater_than",
    "isclose", "less_equal", "less_than", "logical_and", "logical_or",
    "logical_xor", "not_equal",
    "copysign", "hypot", "logaddexp", "nextafter",
}
# math ops needing strictly-positive / unit-interval / special domains
_DOMAIN = {
    "acos": "unit", "asin": "unit", "atanh": "unit", "erfinv": "unit",
    "logit": "unit", "acosh": "pos1", "digamma": "pos", "lgamma": "pos",
    "log": "pos", "log10": "pos", "log1p": "pos", "log2": "pos",
    "rsqrt": "pos", "sqrt": "pos", "reciprocal": "pos",
    "gammaln": "pos", "i0": "pos", "i0e": "pos", "i1": "pos", "i1e": "pos",
}


def smoke_cases(I: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Callable[[], Any]]:
    """'category:name' → zero-arg thunk running one tiny-shape call.

    Thunks re-resolve the implementing callable at run time (through
    op_registry.resolve), so a regressed op fails here rather than being
    silently skipped.

    ``I`` overrides the canonical input dict — :func:`run_batched` passes
    *traced* substitutes so whole groups of thunks stage into one jitted
    program instead of one eager executable per op.
    """
    I = _inputs() if I is None else I
    x, y, m = I["x"], I["y"], I["m"]
    spd, tri, v, vs = I["spd"], I["tri"], I["v"], I["vs"]
    unit, pos, b3, b3t = I["unit"], I["pos"], I["b3"], I["b3t"]
    img, ids, iarr, q = I["img"], I["ids"], I["iarr"], I["q"]
    idx = jnp.asarray([0, 1], jnp.int32)

    # hand-written calls for everything that is not plain f(x) / f(x, y)
    special: Dict[str, Callable[[Callable], Any]] = {
        # creation
        "arange": lambda f: f(0, 6, 1),
        "diag": lambda f: f(v),
        "diagflat": lambda f: f(v),
        "empty": lambda f: f([2, 3]),
        "eye": lambda f: f(3),
        "full": lambda f: f([2, 2], 1.5),
        "full_like": lambda f: f(x, 2.0),
        "linspace": lambda f: f(0.0, 1.0, 5),
        "logspace": lambda f: f(0.0, 1.0, 5),
        "meshgrid": lambda f: f(v, v),
        "ones": lambda f: f([2, 2]),
        "to_tensor": lambda f: f([[1.0, 2.0]]),
        "tril": lambda f: f(m),
        "triu": lambda f: f(m),
        "zeros": lambda f: f([2, 2]),
        # manipulation
        "as_strided": lambda f: f(x, [2, 2], [3, 1]),
        "broadcast_to": lambda f: f(x, [2, 2, 3]),
        "cast": lambda f: f(x, "float16"),
        "chunk": lambda f: f(x, 3, 1),
        "concat": lambda f: f([x, y], 0),
        "expand": lambda f: f(x, [2, 2, 3]),
        "expand_as": lambda f: f(x, jnp.zeros((2, 2, 3))),
        "flip": lambda f: f(x, 0),
        "gather": lambda f: f(x, idx, 0),
        "gather_nd": lambda f: f(x, jnp.asarray([[0, 1], [1, 2]], jnp.int32)),
        "index_select": lambda f: f(x, idx, 1),
        "masked_select": lambda f: f(x, x > 0),
        "moveaxis": lambda f: f(x, 0, 1),
        "put_along_axis": lambda f: f(
            x, jnp.asarray([[0], [1]], jnp.int32),
            jnp.asarray([[9.0], [8.0]], jnp.float32), 1),
        "repeat_interleave": lambda f: f(x, 2, 1),
        "reshape": lambda f: f(x, [3, 2]),
        "roll": lambda f: f(x, 1, 0),
        "rot90": lambda f: f(x),
        "scatter": lambda f: f(x, idx, y),
        "scatter_nd_add": lambda f: f(
            x, jnp.asarray([[0, 1], [1, 2]], jnp.int32),
            jnp.asarray([1.0, 2.0], jnp.float32)),
        "slice": lambda f: f(x, [0], [0], [1]),
        "split": lambda f: f(x, 3, 1),
        "squeeze": lambda f: f(x[:, None]),
        "stack": lambda f: f([x, y], 0),
        "strided_slice": lambda f: f(x, [1], [0], [3], [2]),
        "take_along_axis": lambda f: f(
            x, jnp.asarray([[0], [2]], jnp.int32), 1),
        "tile": lambda f: f(x, [2, 1]),
        "transpose": lambda f: f(x, [1, 0]),
        "unbind": lambda f: f(x, 0),
        "unique": lambda f: f(jnp.asarray([1, 2, 2, 3])),
        "unsqueeze": lambda f: f(x, 0),
        "unstack": lambda f: f(x, 0),
        "view": lambda f: f(x, [3, 2]),
        # math (non-unary/non-binary)
        "add_n": lambda f: f([x, y]),
        "bmm": lambda f: f(b3, b3t),
        "clip": lambda f: f(x, -1.0, 1.0),
        "cross": lambda f: f(x, y),
        "cumprod": lambda f: f(x, 0),
        "dot": lambda f: f(v, v),
        "einsum": lambda f: f("ij,jk->ik", m, m),
        "floor_divide": lambda f: f(pos, jnp.abs(y) + 1.0),
        "inner": lambda f: f(v, v),
        "lerp": lambda f: f(x, y, 0.5),
        "logit": lambda f: f(unit, 1e-6),
        "matmul": lambda f: f(m, m),
        "mm": lambda f: f(m, m),
        "mod": lambda f: f(pos, jnp.abs(y) + 1.0),
        "mv": lambda f: f(m, v),
        "outer": lambda f: f(v, v),
        "remainder": lambda f: f(pos, jnp.abs(y) + 1.0),
        "trace": lambda f: f(m),
        "trapezoid": lambda f: f(v),
        "vander": lambda f: f(v),
        # logic
        "bitwise_and": lambda f: f(iarr, iarr),
        "bitwise_not": lambda f: f(iarr),
        "bitwise_or": lambda f: f(iarr, iarr),
        "bitwise_xor": lambda f: f(iarr, iarr),
        "where": lambda f: f(x > 0, x, y),
        # search
        "bucketize": lambda f: f(x, vs),
        "histogram": lambda f: f(x, 4, -3.0, 3.0),
        "index_sample": lambda f: f(x, jnp.asarray([[0, 1], [2, 0]],
                                                   jnp.int32)),
        "kthvalue": lambda f: f(x, 2),
        "masked_fill": lambda f: f(x, x > 0, 0.0),
        "quantile": lambda f: f(x, 0.5),
        "searchsorted": lambda f: f(vs, x),
        "topk": lambda f: f(x, 2),
        # random
        "bernoulli": lambda f: f(unit),
        "exponential": lambda f: f(pos),
        "multinomial": lambda f: f(unit[0], 2, True),
        "normal": lambda f: f(0.0, 1.0, (2, 2)),
        "poisson": lambda f: f(pos),
        "rand": lambda f: f([2, 2]),
        "randint": lambda f: f(0, 5, [3]),
        "randn": lambda f: f([2, 2]),
        "randperm": lambda f: f(5),
        "shuffle": lambda f: f(x),
        "standard_normal": lambda f: f([2, 2]),
        "uniform": lambda f: f([2, 2]),
        # linalg
        "cholesky": lambda f: f(spd),
        "cholesky_solve": lambda f: f(
            jnp.ones((3, 1), jnp.float32), jnp.linalg.cholesky(spd)),
        "cond": lambda f: f(m),
        "det": lambda f: f(m),
        "dist": lambda f: f(x, y),
        "eig": lambda f: f(m),
        "eigh": lambda f: f(spd),
        "eigvals": lambda f: f(m),
        "eigvalsh": lambda f: f(spd),
        "householder_product": lambda f: f(
            m, jnp.asarray([0.5, 0.3, 0.1], jnp.float32)),
        "inv": lambda f: f(m),
        "lstsq": lambda f: f(m, jnp.ones((3, 1), jnp.float32)),
        "lu": lambda f: f(m),
        "matrix_power": lambda f: f(m, 2),
        "matrix_rank": lambda f: f(m),
        "matrix_transpose": lambda f: f(m),
        "multi_dot": lambda f: f([m, m]),
        "pinv": lambda f: f(m),
        "qr": lambda f: f(m),
        "slogdet": lambda f: f(m),
        "solve": lambda f: f(m, jnp.ones((3,), jnp.float32)),
        "svd": lambda f: f(m),
        "triangular_solve": lambda f: f(tri, jnp.ones((3, 1), jnp.float32)),
        # nn.functional
        "avg_pool2d": lambda f: f(img, 2),
        "conv2d": lambda f: f(img, jnp.ones((3, 4, 2, 2), jnp.float32) * 0.1),
        "cross_entropy": lambda f: f(
            jnp.asarray(np.random.default_rng(1).normal(size=(4, 5)),
                        jnp.float32),
            jnp.asarray([0, 1, 2, 3], jnp.int64)),
        "dropout": lambda f: f(x, 0.5),
        "embedding": lambda f: f(ids, jnp.ones((10, 4), jnp.float32)),
        "group_norm": lambda f: f(img, 2),
        "interpolate": lambda f: f(img, None, 2),
        "layer_norm": lambda f: f(x, [3]),
        "linear": lambda f: f(x, jnp.ones((3, 4), jnp.float32),
                              jnp.zeros((4,), jnp.float32)),
        "max_pool2d": lambda f: f(img, 2),
        "mse_loss": lambda f: f(x, y),
        "one_hot": lambda f: f(ids, 10),
        "pad": lambda f: f(x, [1, 1]),
        "prelu": lambda f: f(x, jnp.asarray([0.2], jnp.float32)),
        "scaled_dot_product_attention": lambda f: f(q, q, q),
        "smooth_l1_loss": lambda f: f(x, y),
        "softmax_with_cross_entropy": lambda f: f(
            jnp.asarray(np.random.default_rng(1).normal(size=(4, 5)),
                        jnp.float32),
            jnp.asarray([[0], [1], [2], [3]], jnp.int64)),
        "swiglu": lambda f: f(x, y),
        "unfold": lambda f: f(img, 2),
        # incubate
        "flash_attention": lambda f: f(q, q, q, causal=True),
        "fused_bias_dropout_residual_layer_norm": lambda f: f(
            x, y, dropout_rate=0.0),
        "fused_multi_transformer": lambda f: _fmt_case(f),
        "variable_length_memory_efficient_attention": lambda f: f(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(q, 1, 2),
            jnp.swapaxes(q, 1, 2), jnp.asarray([6]), jnp.asarray([8])),
        "fused_rms_norm": lambda f: f(x),
        "fused_rotary_position_embedding": lambda f: _rope_case(f),
        "ring_attention": lambda f: _ring_case(f),
        "ssd_scan": lambda f: f(
            jnp.ones((1, 4, 2, 4), jnp.float32),          # x (B,L,H,P)
            jnp.full((1, 4, 2), 0.9, jnp.float32),        # a (B,L,H)
            jnp.ones((1, 4, 1, 4), jnp.float32) * 0.1,    # b (B,L,G,N)
            jnp.ones((1, 4, 1, 4), jnp.float32) * 0.1),   # c
        "wkv": lambda f: f(
            jnp.asarray([0.1, 0.2], jnp.float32),
            jnp.asarray([0.3, 0.1], jnp.float32),
            jnp.ones((1, 4, 2), jnp.float32) * 0.1,
            jnp.ones((1, 4, 2), jnp.float32)),
    }
    special.update(_round4_cases(I))
    special.update(_round5_cases(I))

    cases: Dict[str, Callable[[], Any]] = {}
    for cat, names in op_registry.TARGET_SURFACE.items():
        for name in names:
            cases[f"{cat}:{name}"] = _make_thunk(cat, name, special,
                                                 x, y, unit, pos, idx)
    return cases


def _round5_cases(I):
    """Smoke calls for the round-5 tranche (distribution, autograd
    functional, remaining incubate fusions, weight-only quant, metric,
    amp).  All keys are 'category:name'-qualified."""
    x, unit, pos = I["x"], I["unit"], I["pos"]
    key = jax.random.key(0)

    def dist_case(maker, value, discrete=False, has_entropy=True):
        """Construct → sample → log_prob (→ entropy): the whole method
        surface must lower, not just __init__.  Every result is returned
        (the caller's generic block/scalarize consumes them — keeps the
        thunk traceable for the batched sweep)."""
        def run(cls):
            d = maker(cls)
            s = d.sample((2,), key=key)
            lp = d.log_prob(value)
            ent = d.entropy() if has_entropy else None
            return s, lp, ent
        return run

    half = jnp.asarray(0.4, jnp.float32)
    two = jnp.asarray(2.0, jnp.float32)
    one = jnp.asarray(1.0, jnp.float32)
    simplex = jnp.asarray([0.2, 0.3, 0.5], jnp.float32)

    def transform_case(maker, value):
        """forward → inverse → forward_log_det_jacobian round trip."""
        def run(cls):
            t = maker(cls)
            y = t.forward(value)
            inv = t.inverse(y)
            try:
                ld = t.forward_log_det_jacobian(value)
            except NotImplementedError:
                ld = None  # non-bijective convention transforms (Softmax)
            return y, inv, ld
        return run

    def kl_case(f):
        from .. import distribution as D
        return f(D.Normal(0.0, 1.0), D.Normal(1.0, 2.0))

    def register_kl_case(f):
        from .. import distribution as D

        class _A(D.Normal):
            pass

        @f(_A, _A)
        def _kl(p, q_):
            return D.kl_divergence(
                D.Normal(p.loc, p.scale), D.Normal(q_.loc, q_.scale))

        return D.kl_divergence(_A(0.0, 1.0), _A(0.0, 1.0))

    def pylayer_case(cls):
        class Double(cls):
            @staticmethod
            def forward(ctx, a):
                ctx.save_for_backward(a)
                return 2 * a

            @staticmethod
            def backward(ctx, g):
                return 2 * g

        out = Double.apply(x)
        return out, jax.grad(lambda a: jnp.sum(Double.apply(a)))(x)

    def quant_roundtrip(algo):
        def run(f):
            w = jnp.asarray(np.random.default_rng(3).normal(size=(4, 8)),
                            jnp.float32)
            return f(w, algo=algo)
        return run

    def wol_case(f):
        from ..nn.quant import weight_quantize
        w = jnp.asarray(np.random.default_rng(3).normal(size=(3, 8)),
                        jnp.float32)
        qw, sc = weight_quantize(w)
        return f(x, qw, weight_scale=sc)

    def dequant_case(f):
        from ..nn.quant import weight_quantize
        qw, sc = weight_quantize(jnp.ones((4, 8), jnp.float32))
        return f(qw, sc)

    def metric_case(name):
        def run(cls):
            m = cls()
            if name == "Accuracy":
                m.update(m.compute(jnp.asarray([[0.1, 0.9], [0.8, 0.2]]),
                                   jnp.asarray([[1], [0]])))
            elif name in ("Precision", "Recall"):
                m.update(jnp.asarray([0.9, 0.2]), jnp.asarray([1, 0]))
            elif name == "Auc":
                m.update(jnp.asarray([[0.6, 0.4], [0.3, 0.7]]),
                         jnp.asarray([[0], [1]]))
            return m.accumulate()

        def base(cls):  # Metric: abstract base — subclassable is the API
            class _M(cls):
                def name(self):
                    return "m"

                def update(self, *a):
                    pass

                def accumulate(self):
                    return 0.0

                def reset(self):
                    pass
            _M().update()
            return _M().accumulate()
        return base if name == "Metric" else run

    def autocast_case(f):
        with f(enable=True):
            out = x @ jnp.ones((3, 2), jnp.float32)
        jax.block_until_ready(out)
        return out

    def scaler_case(cls):
        sc = cls(init_loss_scaling=2.0)
        state = sc.init_state()
        return jax.block_until_ready(sc.scale_with(state, jnp.sum(x)))

    def decorate_case(f):
        from ..nn import Linear
        model = Linear(3, 2)
        return f(model, level="O2")

    D = "paddle.distribution"
    return {
        # -- distribution construct/sample/log_prob/entropy ----------------
        f"{D}:Normal": dist_case(lambda c: c(0.0, 1.0), half),
        f"{D}:Uniform": dist_case(lambda c: c(0.0, 1.0), half),
        f"{D}:Laplace": dist_case(lambda c: c(0.0, 1.0), half),
        f"{D}:Gumbel": dist_case(lambda c: c(0.0, 1.0), half),
        f"{D}:Cauchy": dist_case(lambda c: c(0.0, 1.0), half),
        f"{D}:Exponential": dist_case(lambda c: c(one), half),
        f"{D}:StudentT": dist_case(lambda c: c(two, 0.0, 1.0), half),
        f"{D}:Gamma": dist_case(lambda c: c(two, two), half),
        f"{D}:Chi2": dist_case(lambda c: c(two), half),
        f"{D}:Beta": dist_case(lambda c: c(two, two), half),
        f"{D}:Dirichlet": dist_case(lambda c: c(simplex * 3), simplex),
        f"{D}:Bernoulli": dist_case(lambda c: c(half), one),
        f"{D}:Geometric": dist_case(lambda c: c(half), two),
        f"{D}:Poisson": dist_case(lambda c: c(two), two),
        f"{D}:Binomial": dist_case(lambda c: c(jnp.asarray(8), half), two,
                                   has_entropy=False),
        f"{D}:Categorical": dist_case(lambda c: c(jnp.log(simplex)),
                                      jnp.asarray(1)),
        f"{D}:Multinomial": dist_case(lambda c: c(6, simplex),
                                      jnp.asarray([1.0, 2.0, 3.0]),
                                      has_entropy=False),
        f"{D}:MultivariateNormal": dist_case(
            lambda c: c(jnp.zeros(2),
                        covariance_matrix=jnp.asarray([[2.0, 0.5],
                                                       [0.5, 1.0]])),
            jnp.asarray([0.3, -0.2])),
        f"{D}:LKJCholesky": dist_case(
            lambda c: c(3, 1.5), jnp.eye(3), has_entropy=False),
        f"{D}:LogNormal": dist_case(lambda c: c(0.0, 1.0), half,
                                    has_entropy=True),
        f"{D}:ContinuousBernoulli": dist_case(lambda c: c(half), half,
                                              has_entropy=False),
        f"{D}:Independent": dist_case(
            lambda c: (lambda D_: c(D_.Normal(jnp.zeros(3), jnp.ones(3)),
                                    1))(_dist_mod()), jnp.zeros(3)),
        f"{D}:TransformedDistribution": dist_case(
            lambda c: (lambda D_: c(D_.Normal(0.0, 1.0),
                                    [D_.ExpTransform()]))(_dist_mod()),
            pos[0, 0], has_entropy=False),
        f"{D}:Distribution": lambda c: c((), ()).batch_shape,
        f"{D}:ExponentialFamily": lambda c: issubclass(c, object),
        f"{D}:kl_divergence": kl_case,
        f"{D}:register_kl": register_kl_case,
        # -- transforms ----------------------------------------------------
        f"{D}:Transform": lambda c: isinstance(c(), c),
        f"{D}:ExpTransform": transform_case(lambda c: c(), x),
        f"{D}:AbsTransform": transform_case(lambda c: c(), x),
        f"{D}:AffineTransform": transform_case(lambda c: c(1.0, 2.0), x),
        f"{D}:PowerTransform": transform_case(lambda c: c(2.0), pos),
        f"{D}:SigmoidTransform": transform_case(lambda c: c(), x),
        f"{D}:TanhTransform": transform_case(lambda c: c(), unit - 0.5),
        f"{D}:SoftmaxTransform": transform_case(lambda c: c(), x),
        f"{D}:StickBreakingTransform": transform_case(
            lambda c: c(), jnp.asarray([0.3, -0.2])),
        f"{D}:ReshapeTransform": transform_case(
            lambda c: c((3,), (3, 1)), x),
        f"{D}:IndependentTransform": transform_case(
            lambda c: (lambda D_: c(D_.ExpTransform(), 1))(_dist_mod()),
            x),
        f"{D}:ChainTransform": transform_case(
            lambda c: (lambda D_: c([D_.AffineTransform(0.0, 2.0),
                                     D_.ExpTransform()]))(_dist_mod()),
            x),
        f"{D}:StackTransform": transform_case(
            lambda c: (lambda D_: c([D_.ExpTransform(),
                                     D_.TanhTransform()], axis=0))(
                _dist_mod()),
            jnp.stack([x[0], x[1]])),
        # -- autograd functional -------------------------------------------
        "paddle.autograd:grad":
            lambda f: f(lambda a: jnp.sum(a * a))(x),
        "paddle.autograd:jacobian":
            lambda f: f(lambda a: jnp.sin(a), I["v"]),
        "paddle.autograd:hessian":
            lambda f: f(lambda a: jnp.sum(a * a), I["v"]),
        "paddle.autograd:vjp":
            lambda f: f(lambda a: jnp.sum(a * a), x),
        "paddle.autograd:jvp":
            lambda f: f(lambda a: a * a, x),
        "paddle.autograd:no_grad":
            lambda f: f(lambda a: a * 2)(x),
        "paddle.autograd:PyLayer": pylayer_case,
        # -- incubate fusions (round 5) ------------------------------------
        "paddle.incubate:fused_linear":
            lambda f: f(x, jnp.ones((3, 4), jnp.float32),
                        jnp.zeros((4,), jnp.float32)),
        "paddle.incubate:fused_linear_activation":
            lambda f: f(x, jnp.ones((3, 4), jnp.float32),
                        jnp.zeros((4,), jnp.float32), activation="gelu"),
        "paddle.incubate:fused_dropout_add":
            lambda f: f(x, I["y"], p=0.0),
        "paddle.incubate:fused_layer_norm":
            lambda f: f(x, jnp.ones((3,), jnp.float32),
                        jnp.zeros((3,), jnp.float32), 1e-5,
                        residual=I["y"]),
        "paddle.incubate:fused_feedforward":
            lambda f: f(jnp.ones((1, 4, 8), jnp.float32),
                        jnp.ones((8, 16), jnp.float32),
                        jnp.ones((16, 8), jnp.float32),
                        dropout1_rate=0.0, dropout2_rate=0.0,
                        ln2_scale=jnp.ones((8,), jnp.float32)),
        "paddle.incubate:fused_attention": _fused_attention_case,
        "paddle.incubate:masked_multihead_attention": _mmha_case,
        # -- weight-only quant ---------------------------------------------
        "paddle.nn.quant:weight_quantize":
            quant_roundtrip("weight_only_int8"),
        "paddle.nn.quant:weight_dequantize": dequant_case,
        "paddle.nn.quant:weight_only_linear": wol_case,
        "paddle.nn.quant:llm_int8_linear": wol_case,
        # -- metric / amp --------------------------------------------------
        "paddle.metric:Metric": metric_case("Metric"),
        "paddle.metric:Accuracy": metric_case("Accuracy"),
        "paddle.metric:Precision": metric_case("Precision"),
        "paddle.metric:Recall": metric_case("Recall"),
        "paddle.metric:Auc": metric_case("Auc"),
        "paddle.amp:auto_cast": autocast_case,
        "paddle.amp:GradScaler": scaler_case,
        "paddle.amp:decorate": decorate_case,
    }


def _dist_mod():
    from .. import distribution
    return distribution


def _fused_attention_case(f):
    rng = np.random.default_rng(5)
    e, nh, hd = 8, 2, 4
    x = jnp.asarray(rng.normal(size=(1, 4, e)), jnp.float32)
    qkv_w = jnp.asarray(rng.normal(size=(3, nh, hd, e)) * 0.1, jnp.float32)
    lin_w = jnp.asarray(rng.normal(size=(nh * hd, e)) * 0.1, jnp.float32)
    return f(x, qkv_w, lin_w, dropout_rate=0.0, attn_dropout_rate=0.0,
             ln_scale=jnp.ones((e,), jnp.float32))


def _mmha_case(f):
    rng = np.random.default_rng(6)
    b, h, d, max_len = 2, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(b, 3 * h * d)), jnp.float32)
    cache = jnp.zeros((2, b, h, max_len, d), jnp.float32)
    out, cache = f(x, cache,
                   sequence_lengths=jnp.asarray([0, 3], jnp.int32))
    return out


def _round4_cases(I):
    """Smoke calls for the round-4 breadth surface.  Keys are bare names
    when globally unique, 'category:name'-qualified where namespaces
    collide (sparse.matmul vs math.matmul, sparse.nn.relu vs F.relu)."""
    x, y, m, v = I["x"], I["y"], I["m"], I["v"]
    pos, unit, img, b3 = I["pos"], I["unit"], I["img"], I["b3"]
    iarr, ids = I["iarr"], I["ids"]
    idx = jnp.asarray([0, 1], jnp.int32)
    sig = jnp.ones((1, 2, 8), jnp.float32)          # NCL
    vol = jnp.ones((1, 2, 4, 4, 4), jnp.float32)    # NCDHW
    lbl01 = (unit > 0.5).astype(jnp.float32)
    sgn = jnp.sign(y - 0.1)
    logp = jax.nn.log_softmax(jnp.asarray(
        np.random.default_rng(2).normal(size=(4, 5)), jnp.float32))
    boxes = jnp.asarray([[0.0, 0.0, 2.0, 2.0], [1.0, 1.0, 3.0, 3.0]],
                        jnp.float32)

    def _coo(f=None):
        from .. import sparse as sp
        coo = sp.sparse_coo_tensor(
            jnp.asarray([[0, 1], [1, 2]]), jnp.asarray([1.0, 2.0]), (2, 3))
        return coo

    cases = {
        # -- math breadth
        "addmm": lambda f: f(m, m, m),
        "bincount": lambda f: f(jnp.asarray([0, 1, 1, 2])),
        "cdist": lambda f: f(x, x),
        "combinations": lambda f: f(v),
        "cumulative_trapezoid": lambda f: f(v),
        "diag_embed": lambda f: f(v),
        "diagonal": lambda f: f(m),
        "gammainc": lambda f: f(pos, pos),
        "gammaincc": lambda f: f(pos, pos),
        "gcd": lambda f: f(iarr, iarr),
        "lcm": lambda f: f(iarr, iarr),
        "index_add": lambda f: f(x, idx, 0, jnp.ones((2, 3))),
        "index_fill": lambda f: f(x, idx, 0, 1.0),
        "index_put": lambda f: f(
            x, (jnp.asarray([0, 1]), jnp.asarray([1, 2])),
            jnp.asarray([9.0, 9.0])),
        "kron": lambda f: f(m, m),
        "ldexp": lambda f: f(x, iarr),
        "multigammaln": lambda f: f(pos + 3.0, 2),
        "polygamma": lambda f: f(pos, 1),
        "renorm": lambda f: f(x, 2.0, 0, 1.0),
        "take": lambda f: f(x, idx),
        "tensordot": lambda f: f(m, m),
        # -- logic breadth
        "bitwise_left_shift": lambda f: f(iarr, iarr),
        "bitwise_right_shift": lambda f: f(iarr, iarr),
        # -- manipulation breadth (complex cases jitted — see "istft" note)
        "as_complex": lambda f: jax.jit(f)(jnp.ones((3, 2), jnp.float32)),
        "as_real": lambda f: jax.jit(lambda a, b: f(jax.lax.complex(a, b)))(
            x, y),
        "block_diag": lambda f: f([m, m]),
        "column_stack": lambda f: f([x, y]),
        "row_stack": lambda f: f([x, y]),
        "hstack": lambda f: f([x, y]),
        "vstack": lambda f: f([x, y]),
        "dstack": lambda f: f([x, y]),
        "crop": lambda f: f(x, [1, 2], [0, 1]),
        "dsplit": lambda f: f(b3, 2),
        "hsplit": lambda f: f(x, 3),
        "vsplit": lambda f: f(x, 2),
        "tensor_split": lambda f: f(x, 2),
        "unflatten": lambda f: f(x, 1, [3, 1]),
        "unique_consecutive": lambda f: f(jnp.asarray([1, 1, 2])),
        "masked_scatter": lambda f: f(x, x > 0, jnp.ones(6)),
        # -- creation breadth
        "complex": lambda f: f(x, y),
        "polar": lambda f: f(pos, x),
        "tril_indices": lambda f: f(3),
        "triu_indices": lambda f: f(3),
        # -- random breadth
        "log_normal": lambda f: f(0.0, 1.0, (2, 2)),
        "binomial": lambda f: f(jnp.full((2,), 5), unit[0, :2]),
        "standard_gamma": lambda f: f(pos),
        # -- fft
        "fftfreq": lambda f: f(4),
        "rfftfreq": lambda f: f(4),
        "fftshift": lambda f: f(v),
        "ifftshift": lambda f: f(v),
        # -- signal (istft input: an stft roundtrip of a real signal)
        "stft": lambda f: f(jnp.ones((64,), jnp.float32), 16),
        "istft": lambda f: _istft_case(f),
        # -- vision.ops
        "nms": lambda f: f(boxes, 0.5, jnp.asarray([0.9, 0.8])),
        "roi_align": lambda f: f(img, boxes, [2], 2),
        "roi_pool": lambda f: f(img, boxes, [2], 2),
        "box_coder": lambda f: f(boxes, None, boxes + 0.5),
        "prior_box": lambda f: f(img, jnp.zeros((1, 3, 16, 16)), [4.0]),
        "yolo_box": lambda f: f(
            jnp.ones((1, 2 * 7, 2, 2), jnp.float32),
            jnp.asarray([[32, 32]]), [2, 3, 4, 5], 2, 0.01, 16),
        # -- nn.functional breadth (non-unary)
        "glu": lambda f: f(jnp.ones((2, 4), jnp.float32)),
        "gumbel_softmax": lambda f: f(x),
        "maxout": lambda f: f(jnp.ones((1, 4, 3), jnp.float32), 2),
        "rrelu": lambda f: f(x),
        "binary_cross_entropy": lambda f: f(unit, lbl01),
        "binary_cross_entropy_with_logits": lambda f: f(x, lbl01),
        "cosine_embedding_loss": lambda f: f(x, y, jnp.ones((2,))),
        "cosine_similarity": lambda f: f(x, y),
        "dice_loss": lambda f: f(
            jax.nn.softmax(jnp.ones((2, 3, 4))),
            jnp.zeros((2, 3, 1), jnp.int32)),
        "hinge_embedding_loss": lambda f: f(x, sgn),
        "kl_div": lambda f: f(logp, jax.nn.softmax(logp)),
        "l1_loss": lambda f: f(x, y),
        "log_loss": lambda f: f(unit, lbl01),
        "margin_ranking_loss": lambda f: f(v, v + 0.1, jnp.sign(v)),
        "multi_label_soft_margin_loss": lambda f: f(x, lbl01),
        "nll_loss": lambda f: f(logp, jnp.asarray([0, 1, 2, 3])),
        "poisson_nll_loss": lambda f: f(x, pos),
        "sigmoid_focal_loss": lambda f: f(x, lbl01),
        "soft_margin_loss": lambda f: f(x, sgn),
        "square_error_cost": lambda f: f(x, y),
        "triplet_margin_loss": lambda f: f(x, y, x + 1.0),
        "batch_norm": lambda f: f(img, jnp.zeros(4), jnp.ones(4)),
        "instance_norm": lambda f: f(img),
        "local_response_norm": lambda f: f(img, 3),
        "normalize": lambda f: f(x),
        "conv1d": lambda f: f(sig, jnp.ones((3, 2, 2), jnp.float32)),
        "conv3d": lambda f: f(vol, jnp.ones((3, 2, 2, 2, 2), jnp.float32)),
        "conv1d_transpose": lambda f: f(
            sig, jnp.ones((2, 3, 2), jnp.float32), stride=2),
        "conv2d_transpose": lambda f: f(
            img, jnp.ones((4, 3, 2, 2), jnp.float32), stride=2),
        "conv3d_transpose": lambda f: f(
            vol, jnp.ones((2, 3, 2, 2, 2), jnp.float32), stride=2),
        "avg_pool1d": lambda f: f(sig, 2),
        "avg_pool3d": lambda f: f(vol, 2),
        "max_pool1d": lambda f: f(sig, 2),
        "max_pool3d": lambda f: f(vol, 2),
        "adaptive_avg_pool1d": lambda f: f(sig, 2),
        "adaptive_avg_pool2d": lambda f: f(img, 2),
        "adaptive_avg_pool3d": lambda f: f(vol, 2),
        "adaptive_max_pool1d": lambda f: f(sig, 2),
        "adaptive_max_pool2d": lambda f: f(img, 2),
        "affine_grid": lambda f: f(
            jnp.asarray([[[1.0, 0, 0], [0, 1.0, 0]]]), (1, 4, 4, 4)),
        "grid_sample": lambda f: f(img, jnp.zeros((1, 4, 4, 2))),
        "pixel_shuffle": lambda f: f(img, 2),
        "pixel_unshuffle": lambda f: f(img, 2),
        "channel_shuffle": lambda f: f(img, 2),
        "fold": lambda f: f(jnp.ones((1, 8, 4), jnp.float32), (4, 4), 2,
                            strides=2),
        "upsample": lambda f: f(img, None, 2),
        "zeropad2d": lambda f: f(img, [1, 1, 1, 1]),
        "alpha_dropout": lambda f: f(x, 0.3),
        "dropout2d": lambda f: f(img),
        "dropout3d": lambda f: f(vol),
        "label_smooth": lambda f: f(unit),
        "sequence_mask": lambda f: f(jnp.asarray([1, 2]), 3),
        "temporal_shift": lambda f: f(jnp.ones((2, 4, 4, 4)), 2),
        "margin_cross_entropy": lambda f: f(
            unit[:, :3] * 2.0 - 1.0, jnp.asarray([0, 2])),
        "ctc_loss": lambda f: f(
            jax.nn.log_softmax(jnp.ones((6, 2, 5)), axis=-1),
            jnp.asarray([[1, 2, 3], [2, 4, 0]]),
            jnp.asarray([6, 5]), jnp.asarray([3, 2])),
        "matrix_nms": lambda f: f(
            jnp.asarray([[[0.0, 0, 4, 4], [1.0, 1, 5, 5],
                          [8.0, 8, 9, 9]]]),
            jnp.asarray([[[0.0, 0.0, 0.0], [0.9, 0.8, 0.7]]]), 0.1),
        "psroi_pool": lambda f: f(
            jnp.ones((1, 8, 8, 8)), boxes, [2], 2, 1.0, 2, 2),
        "deform_conv2d": lambda f: f(
            jnp.ones((1, 2, 5, 5)), jnp.zeros((1, 2 * 4, 4, 4)),
            jnp.ones((2, 2, 2, 2)) * 0.1),
        "class_center_sample": lambda f: f(jnp.asarray([1, 3]), 8, 4),
        "matrix_exp": lambda f: f(jnp.eye(3) * 0.1),
        "corrcoef": lambda f: f(jnp.asarray(
            np.random.default_rng(3).normal(size=(3, 8)), jnp.float32)),
        "distribute_fpn_proposals": lambda f: f(
            boxes * 16.0, 2, 5, 4, 224, rois_num=[2]),
        "generate_proposals": lambda f: f(
            jnp.ones((1, 2, 3, 3)) * 0.5,
            jnp.zeros((1, 8, 3, 3)), jnp.asarray([[24, 24]]),
            jnp.broadcast_to(jnp.asarray([2.0, 2.0, 10.0, 10.0]),
                             (3, 3, 2, 4)), jnp.ones((3, 3, 2, 4))),
        "yolo_loss": lambda f: f(
            jnp.ones((1, 2 * 7, 2, 2)) * 0.1,
            jnp.asarray([[[0.5, 0.5, 0.3, 0.3]]]), jnp.asarray([[1]]),
            [2, 3, 4, 5], [0, 1], 2, 0.7, 16),
        # -- sparse (qualified: names collide with dense namespaces)
        "paddle.sparse:sparse_coo_tensor": lambda f: f(
            jnp.asarray([[0, 1], [1, 2]]), jnp.asarray([1.0, 2.0]), (2, 3)),
        "paddle.sparse:sparse_csr_tensor": lambda f: f(
            jnp.asarray([0, 1, 2]), jnp.asarray([1, 2]),
            jnp.asarray([1.0, 2.0]), (2, 3)),
        "paddle.sparse:coalesce": lambda f: f(_coo()),
        "paddle.sparse:is_same_shape": lambda f: f(_coo(), _coo()),
        "paddle.sparse:matmul": lambda f: f(_coo(), jnp.ones((3, 2))),
        "paddle.sparse:addmm": lambda f: f(jnp.ones((2, 2)), _coo(),
                                           jnp.ones((3, 2))),
        "paddle.sparse:mv": lambda f: f(_coo(), jnp.ones((3,))),
        "paddle.sparse:transpose": lambda f: f(_coo(), [1, 0]),
        "paddle.sparse:reshape": lambda f: f(_coo(), [3, 2]),
        "paddle.sparse:add": lambda f: f(_coo(), _coo()),
        "paddle.sparse:subtract": lambda f: f(_coo(), _coo()),
        "paddle.sparse:multiply": lambda f: f(_coo(), _coo()),
        "paddle.sparse:divide": lambda f: f(_coo(), _coo()),
        "paddle.sparse:pow": lambda f: f(_coo(), 2.0),
        "paddle.sparse:cast": lambda f: f(_coo(), None, jnp.float32),
        "paddle.sparse:sum": lambda f: f(_coo(), axis=1),
        "paddle.sparse:slice": lambda f: f(_coo(), [0, 1], [0, 0], [2, 2]),
        "paddle.sparse:mask_as": lambda f: f(jnp.ones((2, 3)), _coo()),
        "paddle.sparse:masked_matmul": lambda f: f(
            jnp.ones((2, 3)), jnp.ones((3, 3)), _coo()),
        "paddle.sparse.nn:softmax": lambda f: f(_coo()),
        "paddle.sparse.nn:attention": lambda f: f(
            jnp.ones((1, 1, 2, 4)), jnp.ones((1, 1, 2, 4)),
            jnp.ones((1, 1, 2, 4)), _sq_coo()),
        "paddle.sparse.nn:conv3d": lambda f: _sparse_conv_case(f),
        "paddle.sparse.nn:subm_conv3d": lambda f: _sparse_conv_case(f),
    }
    for name in ("sin", "tan", "asin", "atan", "sinh", "tanh", "asinh",
                 "atanh", "sqrt", "square", "log1p", "abs", "expm1", "neg",
                 "rad2deg", "deg2rad"):
        cases[f"paddle.sparse:{name}"] = (
            lambda f, _n=name: f(_scaled_coo()))
    for name in ("relu", "relu6", "leaky_relu"):
        cases[f"paddle.sparse.nn:{name}"] = lambda f: f(_coo())
    return cases


def _istft_case(f):
    from ..signal import stft

    return f(stft(jnp.ones((64,), jnp.float32), 16), 16)


def _fmt_case(f):
    e, nh, hd, ff = 8, 2, 4, 16
    ones = jnp.ones
    return f(ones((1, 4, e)), [ones(e)], [ones(e) * 0.0],
             [ones((3, nh, hd, e)) * 0.1], [ones((3, nh, hd)) * 0.0],
             [ones((nh * hd, e)) * 0.1], [ones(e) * 0.0],
             [ones(e)], [ones(e) * 0.0],
             [ones((e, ff)) * 0.1], [ones(ff) * 0.0],
             [ones((ff, e)) * 0.1], [ones(e) * 0.0])


def _sq_coo():
    """Square (2, 2) pattern with every row occupied (sparse attention)."""
    from .. import sparse as sp
    return sp.sparse_coo_tensor(
        jnp.asarray([[0, 1], [0, 1]]), jnp.asarray([1.0, 1.0]), (2, 2))


def _sparse_conv_case(f):
    from jax.experimental import sparse as jsparse
    dense = jnp.zeros((1, 3, 3, 3, 2)).at[0, 1, 1, 1].set(1.0)
    x = jsparse.BCOO.fromdense(dense, n_dense=1)
    return f(x, jnp.ones((3, 3, 3, 2, 2)) * 0.1, padding=1)


def _scaled_coo():
    """COO with values in (0, 1): valid for every zero-preserving unary
    domain (atanh/asin need |v| < 1)."""
    from .. import sparse as sp
    return sp.sparse_coo_tensor(
        jnp.asarray([[0, 1], [1, 2]]), jnp.asarray([0.3, 0.6]), (2, 3))


def _nn_layer_thunk(name: str):
    """paddle.nn Layer-class smokes: construct + one tiny forward."""

    def thunk():
        import paddle_tpu as pt
        import paddle_tpu.nn as nn

        pt.seed(0)
        x = jnp.ones((2, 8), jnp.float32)
        img = jnp.ones((1, 4, 6, 6), jnp.float32)
        sig = jnp.ones((1, 4, 8), jnp.float32)
        vol = jnp.ones((1, 2, 4, 4, 4), jnp.float32)
        seq = jnp.ones((2, 5, 8), jnp.float32)
        ids1 = jnp.asarray([0, 1], jnp.int32)
        logp = jax.nn.log_softmax(jnp.ones((2, 8)), axis=-1)

        def loss2(cls, *a, **k):
            return lambda: cls(*a, **k)(x, jnp.ones((2, 8)))

        cases = {
            "Layer": lambda: nn.Layer(),
            "Sequential": lambda: nn.Sequential(nn.Linear(8, 4))(x),
            "LayerList": lambda: nn.LayerList([nn.Linear(8, 4)]),
            "Linear": lambda: nn.Linear(8, 4)(x),
            "Embedding": lambda: nn.Embedding(10, 4)(ids1),
            "Dropout": lambda: nn.Dropout(0.5)(x),
            "Identity": lambda: nn.Identity()(x),
            "Flatten": lambda: nn.Flatten()(img),
            "Unflatten": lambda: nn.Unflatten(1, [2, 4])(x),
            "Conv1D": lambda: nn.Conv1D(4, 3, 2)(sig),
            "Conv2D": lambda: nn.Conv2D(4, 3, 2)(img),
            "Conv3D": lambda: nn.Conv3D(2, 3, 2)(vol),
            "Conv1DTranspose": lambda: nn.Conv1DTranspose(4, 3, 2)(sig),
            "Conv2DTranspose": lambda: nn.Conv2DTranspose(4, 3, 2)(img),
            "Conv3DTranspose": lambda: nn.Conv3DTranspose(2, 3, 2)(vol),
            "BatchNorm": lambda: nn.BatchNorm(4)(img),
            "BatchNorm1D": lambda: nn.BatchNorm1D(4)(sig),
            "BatchNorm2D": lambda: nn.BatchNorm2D(4)(img),
            "BatchNorm3D": lambda: nn.BatchNorm3D(2)(vol),
            "SyncBatchNorm": lambda: nn.SyncBatchNorm(4)(img),
            "InstanceNorm1D": lambda: nn.InstanceNorm1D(4)(sig),
            "InstanceNorm2D": lambda: nn.InstanceNorm2D(4)(img),
            "LayerNorm": lambda: nn.LayerNorm([8])(x),
            "GroupNorm": lambda: nn.GroupNorm(2, 4)(img),
            "RMSNorm": lambda: nn.RMSNorm(8)(x),
            "LocalResponseNorm": lambda: nn.LocalResponseNorm(3)(img),
            "MaxPool1D": lambda: nn.MaxPool1D(2)(sig),
            "MaxPool2D": lambda: nn.MaxPool2D(2)(img),
            "AvgPool1D": lambda: nn.AvgPool1D(2)(sig),
            "AvgPool2D": lambda: nn.AvgPool2D(2)(img),
            "AdaptiveAvgPool1D": lambda: nn.AdaptiveAvgPool1D(2)(sig),
            "AdaptiveAvgPool2D": lambda: nn.AdaptiveAvgPool2D(2)(img),
            "AdaptiveAvgPool3D": lambda: nn.AdaptiveAvgPool3D(2)(vol),
            "AdaptiveMaxPool1D": lambda: nn.AdaptiveMaxPool1D(2)(sig),
            "AdaptiveMaxPool2D": lambda: nn.AdaptiveMaxPool2D(2)(img),
            "PReLU": lambda: nn.PReLU()(x),
            "Maxout": lambda: nn.Maxout(2)(img),
            "GLU": lambda: nn.GLU()(x),
            "SimpleRNN": lambda: nn.SimpleRNN(8, 6)(seq),
            "LSTM": lambda: nn.LSTM(8, 6)(seq),
            "GRU": lambda: nn.GRU(8, 6, direction="bidirect")(seq),
            "SimpleRNNCell": lambda: nn.SimpleRNNCell(8, 6)(x),
            "LSTMCell": lambda: nn.LSTMCell(8, 6)(x),
            "GRUCell": lambda: nn.GRUCell(8, 6)(x),
            "MultiHeadAttention":
                lambda: nn.MultiHeadAttention(8, 2)(seq, seq, seq),
            "TransformerEncoderLayer":
                lambda: nn.TransformerEncoderLayer(8, 2, 16)(seq),
            "TransformerEncoder": lambda: nn.TransformerEncoder(
                lambda: nn.TransformerEncoderLayer(8, 2, 16), 2)(seq),
            "CrossEntropyLoss": lambda: nn.CrossEntropyLoss()(
                x, jnp.asarray([1, 2])),
            "NLLLoss": lambda: nn.NLLLoss()(logp, jnp.asarray([1, 2])),
            "BCELoss": lambda: nn.BCELoss()(
                jax.nn.sigmoid(x), jnp.ones((2, 8))),
            "CTCLoss": lambda: nn.CTCLoss()(
                jax.nn.log_softmax(jnp.ones((6, 2, 5)), axis=-1),
                jnp.asarray([[1, 2], [3, 4]]), jnp.asarray([6, 6]),
                jnp.asarray([2, 2])),
            "MarginRankingLoss": lambda: nn.MarginRankingLoss()(
                x, x + 0.1, jnp.sign(x)),
            "TripletMarginLoss": lambda: nn.TripletMarginLoss()(
                x, x + 0.1, x - 1.0),
            "CosineEmbeddingLoss": lambda: nn.CosineEmbeddingLoss()(
                x, x + 0.1, jnp.ones((2,))),
            "Pad2D": lambda: nn.Pad2D([1, 1, 1, 1])(img),
            "ZeroPad2D": lambda: nn.ZeroPad2D([1, 1, 1, 1])(img),
            "Upsample": lambda: nn.Upsample(scale_factor=2)(img),
            "UpsamplingBilinear2D":
                lambda: nn.UpsamplingBilinear2D(scale_factor=2)(img),
            "UpsamplingNearest2D":
                lambda: nn.UpsamplingNearest2D(scale_factor=2)(img),
            "PixelShuffle": lambda: nn.PixelShuffle(2)(img),
            "PixelUnshuffle": lambda: nn.PixelUnshuffle(2)(img),
            "ChannelShuffle": lambda: nn.ChannelShuffle(2)(img),
            "Unfold": lambda: nn.Unfold(2)(img),
            "Fold": lambda: nn.Fold((6, 6), 2, strides=2)(
                jnp.ones((1, 16, 9))),
            "CosineSimilarity": lambda: nn.CosineSimilarity()(x, x + 1.0),
            "Dropout2D": lambda: nn.Dropout2D()(img),
            "Dropout3D": lambda: nn.Dropout3D()(vol),
            "AlphaDropout": lambda: nn.AlphaDropout()(x),
        }
        if name in cases:
            out = cases[name]()
        else:
            # activation / simple loss layers: ctor() then forward(x)
            cls = getattr(nn, name)
            inst = cls()
            out = (inst(x, jnp.ones((2, 8)))
                   if name.endswith("Loss") else inst(x))
        for leaf in jax.tree_util.tree_leaves(out):
            if isinstance(leaf, jax.Array):
                jax.block_until_ready(leaf)
        return out
    return thunk


def _tensor_method_thunk_checked(name: str):
    inner = _tensor_method_thunk(name)

    def thunk():
        table = op_registry.resolve()["paddle.Tensor"]
        if table.get(name) is None:
            raise Absent(f"paddle.Tensor:{name} on the absent work queue")
        return inner()
    return thunk


def _tensor_method_thunk(name: str):
    """paddle.Tensor method smokes: call each facade method with minimal
    args on a live on-device tensor."""
    from ..tensor.tensor_facade import Tensor

    def thunk():
        t = Tensor(jnp.asarray([[1.0, 2.0], [3.0, 4.0]]))
        scalar = Tensor(jnp.asarray(2.5))
        calls = {
            "astype": lambda: t.astype("int32"),
            "clone": lambda: t.clone(),
            "cpu": lambda: t.cpu(),
            "detach": lambda: t.detach(),
            "dim": lambda: t.dim(),
            "element_size": lambda: t.element_size(),
            "item": lambda: scalar.item(),
            "ndimension": lambda: t.ndimension(),
            "numel": lambda: t.numel(),
            "numpy": lambda: t.numpy(),
            "to": lambda: t.to("float32"),
            "tolist": lambda: t.tolist(),
            "value_counts": lambda: t.value_counts(),
            "to_dense": lambda: t.to_dense(),
            "to_sparse_coo": lambda: t.to_sparse_coo(),
        }
        if name not in calls:
            raise RuntimeError(f"paddle.Tensor:{name} has no smoke case")
        out = calls[name]()
        val = out.value if isinstance(out, Tensor) else out
        if isinstance(val, jax.Array):
            jax.block_until_ready(val)
        return out
    return thunk


def _rope_case(f):
    from ..ops.rope import build_rope_cache
    q = jnp.ones((1, 4, 2, 8), jnp.float32)
    cos, sin = build_rope_cache(4, 8)
    return f(q, q, cos, sin)


def _single_device_group():
    """An AxisGroup over a 1-device mesh of the default backend — collective
    semantics at world size 1, which is what one bench chip gives us."""
    from jax.sharding import Mesh
    from ..distributed.collective import AxisGroup
    devs = np.asarray(jax.devices()[:1])
    mesh = Mesh(devs, ("x",))
    return AxisGroup("x", mesh), mesh


def _ring_case(f):
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:1])
    mesh = Mesh(devs, ("sep",))
    q = jnp.ones((1, 8, 2, 16), jnp.float32)
    return f(q, q, q, causal=True, mesh=mesh)


def _collective_thunk(name: str, fn, x):
    group, mesh = _single_device_group()
    if name == "barrier":
        return fn(group)
    if name in ("send", "isend"):
        return fn(x, 0, 0, group)
    if name in ("recv", "irecv"):
        return fn(x, 0, 0, group)
    return fn(x, group=group)


def _optimizer_thunk(name: str, fn, x):
    if name == "Optimizer":  # abstract base: constructing it is the smoke
        return fn(learning_rate=0.1)
    o = fn(learning_rate=0.1) if name != "Lamb" else fn(0.1)
    p = {"w": x}
    s = o.init(p)
    new_p, s = o.update({"w": jnp.ones_like(x)}, s, p)
    return new_p


def _lr_thunk(name: str, fn):
    kwargs = {
        "ConstantLR": dict(learning_rate=0.1),
        "LRScheduler": dict(learning_rate=0.1),
        "CosineAnnealingDecay": dict(learning_rate=0.1, T_max=10),
        "ExponentialDecay": dict(learning_rate=0.1, gamma=0.9),
        "LinearWarmup": dict(learning_rate=0.1, warmup_steps=5),
        "MultiStepDecay": dict(learning_rate=0.1, milestones=[2, 4]),
        "NoamDecay": dict(d_model=8, warmup_steps=5),
        "PolynomialDecay": dict(learning_rate=0.1, decay_steps=5),
        "StepDecay": dict(learning_rate=0.1, step_size=2),
    }[name]
    sched = fn(**kwargs)
    if name == "LRScheduler":  # abstract base: get_lr is subclass-provided
        return sched
    sched.step()
    return sched.get_lr()


class Absent(Exception):
    """Raised for registry names on the declared absent work queue — the
    sweep skips them (the CPU-lane floor test owns absence accounting)."""


def _make_thunk(cat: str, name: str, special, x, y, unit, pos, idx):
    if cat == "paddle.Tensor":
        return _tensor_method_thunk_checked(name)
    if cat == "paddle.nn":
        return _nn_layer_thunk(name)

    def thunk():
        table = op_registry.resolve()[cat]
        fn = table.get(name)
        if fn is None:
            raise Absent(f"{cat}:{name} on the absent work queue")
        if f"{cat}:{name}" in special:
            out = special[f"{cat}:{name}"](fn)
        elif cat == "paddle.distributed":
            out = _collective_thunk(name, fn, x)
        elif cat == "paddle.optimizer":
            out = _optimizer_thunk(name, fn, x)
        elif cat == "paddle.optimizer.lr":
            out = _lr_thunk(name, fn)
        elif name in special:
            out = special[name](fn)
        elif name in _BINARY:
            out = fn(x, y)
        else:       # (paddle.fft: irfft* treat the real input as spectra)
            dom = _DOMAIN.get(name)
            arg = {None: x, "unit": unit, "pos": pos,
                   "pos1": pos + 1.0}[dom]
            out = fn(arg)
        # force execution (lowering bugs surface at run, not trace, time)
        for leaf in jax.tree_util.tree_leaves(out):
            if isinstance(leaf, jax.Array):
                jax.block_until_ready(leaf)
        return out
    return thunk


def run(names: Optional[List[str]] = None) -> Dict[str, str]:
    """Run all (or the named) smoke cases; return {case: error} failures.
    Names on the registry's declared absent queue are skipped, not failed
    (the CPU-lane registry test owns absence accounting and its ceiling)."""
    cases = smoke_cases()
    failures: Dict[str, str] = {}
    for key, thunk in cases.items():
        if names is not None and key not in names:
            continue
        try:
            thunk()
        except Absent:
            continue
        except Exception as e:  # noqa: BLE001 — report, don't mask, per-op
            failures[key] = f"{type(e).__name__}: {e}"
    return failures


def _scalarize(out) -> Any:
    """Collapse a thunk's output pytree to one fp32 scalar (the group
    programs' single fetched value — every op's result feeds it, so
    nothing is dead-code-eliminated)."""
    total = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(out):
        if isinstance(leaf, jax.Array) or isinstance(leaf, jnp.ndarray):
            a = leaf
            if jnp.issubdtype(a.dtype, jnp.complexfloating):
                a = jnp.abs(a)
            elif not jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(jnp.float32)
            total = total + jnp.sum(a.astype(jnp.float32))
    return total


# categories whose thunks are host-side by nature (python loops over
# concrete floats, numpy metric accumulation, facade attribute probing,
# context managers asserting concrete dtypes) — sent straight to the
# per-op eager path instead of wasting a group bisection on them
_EAGER_CATEGORIES = {"paddle.optimizer", "paddle.optimizer.lr",
                     "paddle.metric", "paddle.amp", "paddle.Tensor"}


def run_batched(names: Optional[List[str]] = None,
                group_size: int = 32,
                verbose: bool = False) -> Dict[str, str]:
    """The sweep, batched (round-4 verdict #2).

    :func:`run` executes one eager thunk per op — a per-op executable
    compile and fetch each.  Here the canonical input arrays become *jit
    arguments*: each group of ``group_size`` thunks is rebuilt around the
    traced substitutes (``smoke_cases(I_traced)``) inside ONE jitted
    program whose single scalar output (every op's result folded in —
    nothing DCE-able) is the only fetch.  One compile + one fetch per
    group.

    A group that fails to trace/compile/run is bisected: halves retry as
    smaller programs, singletons fall back to the eager path — so error
    attribution is exactly :func:`run`'s.  Host-logic categories
    (optimizer/metric/amp/Tensor) skip straight to eager.  Ops whose
    thunks build their own inputs (creation ops) execute eagerly at trace
    time inside the group — they still ride the group's single fetch.
    Same contract as :func:`run`."""
    I0 = _inputs()
    arr_keys = sorted(k for k, v in I0.items()
                      if isinstance(v, jax.Array))
    table = op_registry.resolve()
    failures: Dict[str, str] = {}

    all_keys = [k for k in smoke_cases(I0)
                if names is None or k in names]
    batch_keys: List[str] = []
    eager_keys: List[str] = []
    for key in all_keys:
        cat, name = key.split(":", 1)
        if cat in _EAGER_CATEGORIES:
            eager_keys.append(key)
        elif table.get(cat, {}).get(name) is None:
            continue                      # declared-absent: skip, as run()
        else:
            batch_keys.append(key)

    def group_program(arrs, keys):
        I_t = dict(I0)
        I_t.update(zip(arr_keys, arrs))
        cases_t = smoke_cases(I_t)
        total = jnp.float32(0.0)
        for k in keys:
            total = total + _scalarize(cases_t[k]())
        return total

    arrs0 = [I0[k] for k in arr_keys]

    from . import random as _frandom

    def run_group(keys):
        if not keys:
            return
        # thunks may reseed the global RNG chain (pt.seed inside the nn
        # Layer cases); under a group TRACE that stores a traced key into
        # the global — a leaked tracer poisoning every later eager thunk.
        # Snapshot/restore the chain around each group attempt.
        g = _frandom._globals()
        saved = (g.key, g.counter, g.guard)
        try:
            prog = jax.jit(lambda arrs: group_program(arrs, tuple(keys)))
            val = float(prog(arrs0))
            if verbose:
                print(f"group of {len(keys)}: ok (scalar {val:.3g})")
        except Exception:  # noqa: BLE001 — bisect down to the culprit
            if len(keys) == 1:
                eager_keys.append(keys[0])
            else:
                mid = len(keys) // 2
                run_group(keys[:mid])
                run_group(keys[mid:])
        finally:
            g.key, g.counter, g.guard = saved

    for i in range(0, len(batch_keys), group_size):
        run_group(batch_keys[i:i + group_size])

    if eager_keys:
        failures.update(run(names=eager_keys))
    if verbose:
        print(f"batched sweep: {len(batch_keys)} batch-eligible in "
              f"{(len(batch_keys) + group_size - 1) // group_size} "
              f"groups, {len(eager_keys)} eager, {len(failures)} failed")
    return failures


if __name__ == "__main__":
    fails = run()
    print(f"{len(smoke_cases()) - len(fails)} ok (incl. skipped-absent), "
          f"{len(fails)} failed")
    for k, v in sorted(fails.items()):
        print(f"  FAIL {k}: {v[:200]}")
