"""OpTest tail coverage + enforcement (round-2 verdict #6).

Every differentiable defop in the registry must have an OpCase (here or in
test_ops_numeric.py) or an explicit waiver entry with a reason; the
enforcement test fails on any unwaived gap, on a stale waiver, and on the
waiver list reaching 40. Reference discipline: test/legacy_test/op_test.py:418
+ test/white_list/ waiver pattern.
"""
import numpy as np
import pytest
import scipy.special as sps

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from op_test import OpCase

S = (4, 5)


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# deterministic integer/index fixtures closed over by case fns
_IDX3 = np.array([2, 0, 3], "int64")
_IDS = np.array([[1, 3, 0], [2, 2, 1]], "int64")
_LBL4 = np.array([1, 0, 3, 2], "int64")
_MASK = (np.arange(20).reshape(4, 5) % 3 == 0)


def _conv2d_ref(x, w):
    n, ci, h, wd = x.shape
    co, _, kh, kw = w.shape
    out = np.zeros((n, co, h - kh + 1, wd - kw + 1), x.dtype)
    for i in range(out.shape[2]):
        for j in range(out.shape[3]):
            patch = x[:, :, i:i + kh, j:j + kw]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out


def _conv1d_ref(x, w):
    n, ci, l = x.shape
    co, _, k = w.shape
    out = np.zeros((n, co, l - k + 1), x.dtype)
    for i in range(out.shape[2]):
        out[:, :, i] = np.einsum("ncl,ocl->no", x[:, :, i:i + k], w)
    return out


def _conv3d_ref(x, w):
    n, ci, d, h, wd = x.shape
    co, _, kd, kh, kw = w.shape
    out = np.zeros((n, co, d - kd + 1, h - kh + 1, wd - kw + 1), x.dtype)
    for a in range(out.shape[2]):
        for i in range(out.shape[3]):
            for j in range(out.shape[4]):
                patch = x[:, :, a:a + kd, i:i + kh, j:j + kw]
                out[:, :, a, i, j] = np.einsum("ncdhw,ocdhw->no", patch, w)
    return out


def _conv2d_transpose_ref(x, w):
    n, ci, h, wd = x.shape
    _, co, kh, kw = w.shape
    out = np.zeros((n, co, h + kh - 1, wd + kw - 1), x.dtype)
    for i in range(h):
        for j in range(wd):
            out[:, :, i:i + kh, j:j + kw] += np.einsum(
                "nc,cohw->nohw", x[:, :, i, j], w)
    return out


def _avg_pool2d_ref(x, k=2):
    n, c, h, w = x.shape
    return x.reshape(n, c, h // k, k, w // k, k).mean(axis=(3, 5))


def _max_pool2d_ref(x, k=2):
    n, c, h, w = x.shape
    return x.reshape(n, c, h // k, k, w // k, k).max(axis=(3, 5))


def _bn_ref(x, g, b):
    m = x.mean(axis=(0, 2, 3), keepdims=True)
    v = x.var(axis=(0, 2, 3), keepdims=True)
    xn = (x - m) / np.sqrt(v + 1e-5)
    return xn * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)


def _gn_ref(x, g, b, groups=2):
    n, c, h, w = x.shape
    xg = x.reshape(n, groups, c // groups, h, w)
    m = xg.mean(axis=(2, 3, 4), keepdims=True)
    v = xg.var(axis=(2, 3, 4), keepdims=True)
    xn = ((xg - m) / np.sqrt(v + 1e-5)).reshape(n, c, h, w)
    return xn * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)


def _in_ref(x, g, b):
    m = x.mean(axis=(2, 3), keepdims=True)
    v = x.var(axis=(2, 3), keepdims=True)
    return (x - m) / np.sqrt(v + 1e-5) * g.reshape(1, -1, 1, 1) \
        + b.reshape(1, -1, 1, 1)


def _lrn_ref(x, n=5, k=1.0, alpha=1e-4, beta=0.75):
    # reference local_response_norm is an avg_pool over the squared window
    # (zero-padded, always / n) — norm.py:654 avg_pool2d then scale(alpha)
    c = x.shape[1]
    sq = np.zeros_like(x)
    half = n // 2
    for i in range(c):
        lo, hi = max(0, i - half), min(c, i + half + 1)
        sq[:, i] = (x[:, lo:hi] ** 2).sum(axis=1)
    return x / (k + alpha * sq / n) ** beta


def _rms_norm_ref(x, g):
    return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g


def _frame_ref(x, frame_length, hop_length):
    n = (x.shape[-1] - frame_length) // hop_length + 1
    return np.stack([x[..., i * hop_length:i * hop_length + frame_length]
                     for i in range(n)], axis=-1)


def _overlap_add_ref(x, hop_length):
    # x: (..., frame_length, num_frames)
    fl, n = x.shape[-2], x.shape[-1]
    out = np.zeros(x.shape[:-2] + (fl + hop_length * (n - 1),), x.dtype)
    for t in range(n):
        out[..., t * hop_length:t * hop_length + fl] += x[..., :, t]
    return out


# ---- fixture-dependent refs / fns used by the cases below --------------------------------
_HINGE_LBL = np.sign(_MASK.astype("float64") - 0.5)


_CE_LBL = np.where(np.arange(4) % 2 == 0, 1, -1).astype("int64")


def _cosine_embedding_ref(a, b):
    cos = (a * b).sum(1) / (np.sqrt((a ** 2).sum(1))
                            * np.sqrt((b ** 2).sum(1)))
    loss = np.where(_CE_LBL > 0, 1.0 - cos, np.maximum(0.0, cos - 0.2))
    return loss.mean()


def _chan_scale(x):
    return np.maximum(np.abs(x).max(axis=0, keepdims=True), 1e-8)


def _fcqd_fn(x):
    from paddle_tpu.quantization import _fake_qdq_channel

    # scale through dispatched ops (not x.numpy()) so the case stays
    # jit-capturable — the static-consistency lane traces this fn
    s = paddle.max(paddle.abs(x), axis=0)
    return _fake_qdq_channel(x, s, bits=8, axis=1)


_WOL_RNG = np.random.RandomState(11)
_WOL_W = _WOL_RNG.randn(5, 3).astype("float32")
_WOL_Q = np.clip(np.round(_WOL_W / (np.abs(_WOL_W).max(0) / 127)),
                 -127, 127).astype(np.int8)
_WOL_S = (np.abs(_WOL_W).max(0) / 127).astype("float32")


def _wol_fn(x):
    from paddle_tpu.quantization.weight_only import _wol

    return _wol(x, paddle.to_tensor(_WOL_Q), paddle.to_tensor(_WOL_S))


_BILINEAR_W = None


def _get_bilinear_w():
    global _BILINEAR_W
    if _BILINEAR_W is None:
        _BILINEAR_W = paddle.to_tensor(
            np.random.RandomState(5).randn(6, 3, 5).astype("float32"))
    return _BILINEAR_W


def _huber_fn(x, y):
    from paddle_tpu.nn.functional.loss import huber_loss

    return huber_loss(x, y, delta=0.7)


def sps_expit_t(x):
    return paddle.nn.functional.sigmoid(x)


def _dice_ref(p):
    oh = np.eye(p.shape[-1])[_LBL4]
    inter = (p * oh).sum(axis=1)
    union = p.sum(axis=1) + oh.sum(axis=1)
    return np.mean(1.0 - (2 * inter + 1e-5) / (union + 1e-5))


def _index_add_ref(x, v):
    out = np.zeros_like(x)
    for k, i in enumerate(_IDX3):
        out[i] += v[k]
    return out


def _index_fill_ref(x, val):
    out = x.copy()
    out[_IDX3] = val
    return out


def _index_put_ref(x, v):
    out = x.copy()
    out[np.array([0, 2])] = v
    return out


def _put_along_ref(x, v):
    out = x.copy()
    np.put_along_axis(out, _IDS[:, :1] % 4, v, 0)
    return out


def _scatter_ref(x, u):
    out = x.copy()
    out[np.array([1, 3])] = u
    return out


def _scatter_nd_add_ref(x, u):
    out = x.copy()
    out[1] += u[0]
    out[3] += u[1]
    return out


def _masked_scatter_ref(x, v):
    out = x.copy()
    out[_MASK] = v[:_MASK.sum()]
    return out


def _mode_ref(x):
    out = []
    for row in x:
        vals, counts = np.unique(row, return_counts=True)
        out.append(vals[np.argmax(counts[::-1][::-1] * 0 + counts)]
                   if False else vals[counts == counts.max()].min())
    return np.array(out)


def _multi_margin_ref(x):
    n, c = x.shape
    correct = x[np.arange(n), _LBL4][:, None]
    margins = np.maximum(0.0, 1.0 - correct + x)
    margins[np.arange(n), _LBL4] = 0.0
    return (margins.sum(1) / c).mean()


def _npair_ref(a, p):
    logits = a @ p.T
    lbl = _LBL4
    sim = (lbl[:, None] == lbl[None, :]).astype("float64")
    sim = sim / sim.sum(1, keepdims=True)
    logp = logits - sps.logsumexp(logits, axis=1, keepdims=True)
    return -(sim * logp).sum(1).mean()


def _focal_ref(x, gamma=2.0, alpha=0.25):
    y = _MASK.astype("float64")
    p = sps.expit(x)
    ce = -(y * np.log(p) + (1 - y) * np.log(1 - p))
    pt = y * p + (1 - y) * (1 - p)
    al = y * alpha + (1 - y) * (1 - alpha)
    return (al * (1 - pt) ** gamma * ce).mean()


def _bn_train_fn(x, g, b):
    rm = paddle.zeros([3])
    rv = paddle.ones([3])
    return F.batch_norm(x, rm, rv, weight=g, bias=b, training=True,
                        epsilon=1e-5)


def _bn_infer_fn(x, g, b):
    rm = paddle.zeros([3], dtype=str(x.dtype))
    rv = paddle.ones([3], dtype=str(x.dtype))
    return F.batch_norm(x, rm, rv, weight=g, bias=b, training=False,
                        epsilon=1e-5)


def _rms_norm_fn(x, g):
    from paddle_tpu.nn.functional.norm import rms_norm

    return rms_norm(x, g, epsilon=1e-6)


def _fused_rms_norm_fn(x, g):
    from paddle_tpu.incubate.nn.functional import fused_rms_norm

    out = fused_rms_norm(x, norm_weight=g, norm_bias=None, epsilon=1e-6,
                         begin_norm_axis=1)
    return out[0] if isinstance(out, tuple) else out


_GSU_SRC = np.array([0, 1, 2, 0])
_GSU_DST = np.array([1, 2, 1, 0])


def _gsu_fn(x, y):
    import paddle_tpu.geometric as G

    return G.send_uv(x, y, paddle.to_tensor(_GSU_SRC),
                     paddle.to_tensor(_GSU_DST), "mul")


def _gsu_ref(x, y):
    return x[_GSU_SRC] * y[_GSU_DST]


_FLCE_LABELS = np.random.RandomState(11).randint(0, 13, (2, 9))
_FLCE_LABELS[0, :2] = -100  # exercise ignore_index and the pad path (9 % 4)


def _flce_fn(h, w):
    from paddle_tpu.incubate.nn.functional import fused_linear_cross_entropy

    return fused_linear_cross_entropy(
        h, w, paddle.to_tensor(_FLCE_LABELS), ignore_index=-100, chunk_size=4)


def _flce_ref(h, w):
    logits = np.asarray(h) @ np.asarray(w)
    m = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - m).sum(-1)) + m[..., 0]
    safe = np.where(_FLCE_LABELS == -100, 0, _FLCE_LABELS)
    picked = np.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return np.where(_FLCE_LABELS == -100, 0.0, lse - picked).astype(logits.dtype)


def _fused_ln_fn(x, g, b):
    from paddle_tpu.incubate.nn.functional import fused_layer_norm

    out = fused_layer_norm(x, norm_weight=g, norm_bias=b, epsilon=1e-5,
                           begin_norm_axis=1)
    return out[0] if isinstance(out, tuple) else out


def _temporal_shift_ref(x, seg_num=2, shift_ratio=0.25):
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    out = np.zeros_like(xr)
    out[:, :-1, :fold] = xr[:, 1:, :fold]                 # shift left
    out[:, 1:, fold:2 * fold] = xr[:, :-1, fold:2 * fold]  # shift right
    out[:, :, 2 * fold:] = xr[:, :, 2 * fold:]
    return out.reshape(nt, c, h, w)


def _unfold_ref(x, k=2):
    n, c, h, w = x.shape
    cols = []
    for i in range(h - k + 1):
        for j in range(w - k + 1):
            cols.append(x[:, :, i:i + k, j:j + k].reshape(n, -1))
    return np.stack(cols, axis=-1)


def _softmax_triu_ref(x):
    s = x.shape[-1]
    mask = np.tril(np.ones((s, s))) > 0
    z = np.where(mask, x, -1e30)
    return _np_softmax(z, -1)


def _affine_grid_ref(theta):
    ys, xs = np.meshgrid([-1.0, 1.0], [-1.0, 1.0], indexing="ij")
    base = np.stack([xs.ravel(), ys.ravel(), np.ones(4)], 1)  # (4, 3)
    out = base @ theta[0].T  # (4, 2)
    return out.reshape(1, 2, 2, 2)


_SPD = None


def _spd():
    global _SPD
    if _SPD is None:
        r = np.random.RandomState(7)
        a = r.randn(4, 4)
        _SPD = a @ a.T + 4.0 * np.eye(4)
    return _SPD


def _chol_solve_fn(b):
    u = paddle.to_tensor(
        np.linalg.cholesky(_spd()).astype(str(b.dtype)))
    return paddle.linalg.cholesky_solve(b, u, upper=False)


def _chol_solve_ref(b):
    return np.linalg.solve(_spd(), b)


def _chol_inverse_fn(x):
    u = paddle.to_tensor(
        np.linalg.cholesky(_spd()).astype(str(x.dtype)))
    return paddle.linalg.cholesky_inverse(u, upper=False) + x * 0.0


def _chol_inverse_ref(x):
    return np.linalg.inv(_spd()) + x * 0.0


_BOX_PRIOR = np.array([[0, 0, 10, 10], [5, 5, 20, 20], [1, 2, 3, 4]],
                      "float32")


def _box_coder_fn(d):
    from paddle_tpu.vision.ops import box_coder

    return box_coder(paddle.to_tensor(_BOX_PRIOR),
                     [0.1, 0.1, 0.2, 0.2], d.unsqueeze(0),
                     code_type="decode_center_size", axis=0).squeeze(0)


def _box_coder_ref(d):
    pb = _BOX_PRIOR.astype("float64")
    pw = pb[:, 2] - pb[:, 0]
    ph = pb[:, 3] - pb[:, 1]
    px = pb[:, 0] + pw / 2
    py = pb[:, 1] + ph / 2
    v = np.array([0.1, 0.1, 0.2, 0.2])
    cx = v[0] * d[:, 0] * pw + px
    cy = v[1] * d[:, 1] * ph + py
    w = np.exp(v[2] * d[:, 2]) * pw
    h = np.exp(v[3] * d[:, 3]) * ph
    return np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], 1)




TAIL_CASES = [
    # ---- trivial elementwise / aliases ------------------------------------
    OpCase("assign", paddle.assign, lambda x: x, [S]),
    OpCase("cast", lambda x: paddle.cast(x, "float32"),
           lambda x: x.astype(x.dtype), [S],
           fp64=False),  # the case itself casts to f32 by design
    OpCase("positive", paddle.positive, lambda x: +x, [S]),
    OpCase("sgn", paddle.sgn, np.sign, [S], grad=False),
    OpCase("sinc", paddle.sinc, np.sinc, [S]),
    OpCase("log_sigmoid", F.log_sigmoid, lambda x: np.log(sps.expit(x)), [S]),
    OpCase("sigmoid_fn", F.sigmoid, sps.expit, [S]),
    OpCase("tanh_fn", F.tanh, np.tanh, [S]),
    OpCase("remainder", paddle.remainder,
           lambda x, y: np.mod(x, y), [S, S], positive=True, grad=False),
    OpCase("ldexp", paddle.ldexp,
           lambda x, y: x * 2.0 ** y, [S, S], dtypes=("float32",)),
    OpCase("ones_like", paddle.ones_like, np.ones_like, [S], grad=False),
    OpCase("zeros_like", paddle.zeros_like, np.zeros_like, [S], grad=False),
    OpCase("angle", paddle.angle,
           lambda x: np.angle(x + 0j), [S], grad=False),
    OpCase("conj", paddle.conj, np.conj, [S]),
    OpCase("real", paddle.real, np.real, [S]),
    OpCase("imag", paddle.imag, np.imag, [S], grad=False),
    OpCase("gammaln", paddle.gammaln, sps.gammaln, [S], positive=True),
    OpCase("polygamma", lambda x: paddle.polygamma(x + 1.0, 1),
           lambda x: sps.polygamma(1, x + 1.0), [S], positive=True,
           grad=False),
    OpCase("gammainc", lambda x: paddle.gammainc(x + 1.0, x + 2.0),
           lambda x: sps.gammainc(x + 1.0, x + 2.0), [S], positive=True,
           grad=False),
    OpCase("gammaincc", lambda x: paddle.gammaincc(x + 1.0, x + 2.0),
           lambda x: sps.gammaincc(x + 1.0, x + 2.0), [S], positive=True,
           grad=False),
    OpCase("multigammaln", lambda x: paddle.multigammaln(x + 3.0, 2),
           lambda x: sps.multigammaln(x + 3.0, 2) if np.ndim(x) == 0
           else np.vectorize(lambda v: sps.multigammaln(v + 3.0, 2))(x),
           [S], positive=True, grad=False),
    # ---- complex constructors ---------------------------------------------
    OpCase("complex", paddle.complex,
           lambda re, im: re + 1j * im, [S, S], grad=False, dtypes=("float32",)),
    OpCase("polar", paddle.polar,
           lambda r, t: r * np.cos(t) + 1j * r * np.sin(t),
           [S, S], positive=True, grad=False, dtypes=("float32",)),
    OpCase("as_complex", paddle.as_complex,
           lambda x: x[..., 0] + 1j * x[..., 1], [(4, 5, 2)], grad=False, dtypes=("float32",)),
    OpCase("as_real", lambda x: paddle.as_real(paddle.complex(x, x * 2.0)),
           lambda x: np.stack([x, x * 2.0], -1), [S], grad=False, dtypes=("float32",)),
    # ---- manipulation ------------------------------------------------------
    OpCase("getitem", lambda x: x[1:3, ::2], lambda x: x[1:3, ::2], [S]),
    OpCase("slice_op",
           lambda x: paddle.slice(x, axes=[0, 1], starts=[1, 0],
                                  ends=[3, 4]),
           lambda x: x[1:3, 0:4], [S]),
    OpCase("split_op", lambda x: paddle.split(x, 2, axis=0)[1],
           lambda x: np.split(x, 2, axis=0)[1], [S]),
    OpCase("flatten_op", lambda x: paddle.flatten(x, 1, 2),
           lambda x: x.reshape(2, 12, 2), [(2, 3, 4, 2)]),
    OpCase("unflatten", lambda x: paddle.unflatten(x, 1, (2, 5)),
           lambda x: x.reshape(4, 2, 5), [(4, 10)]),
    OpCase("unfold", lambda x: paddle.Tensor.unfold(x, 1, 3, 2),
           lambda x: np.stack([x[:, 0:3], x[:, 2:5]], 1), [(4, 5)]),
    OpCase("matrix_transpose", paddle.matrix_transpose,
           lambda x: np.swapaxes(x, -1, -2), [(2, 4, 5)]),
    OpCase("take", lambda x: paddle.take(x, paddle.to_tensor(_IDX3)),
           lambda x: x.reshape(-1)[_IDX3], [S]),
    OpCase("pad_op",
           lambda x: F.pad(x, [1, 2], mode="constant", value=0.5),
           lambda x: np.pad(x, [(0, 0), (1, 2)], constant_values=0.5), [S]),
    OpCase("where_op",
           lambda x, y: paddle.where(paddle.to_tensor(_MASK), x, y),
           lambda x, y: np.where(_MASK, x, y), [S, S]),
    OpCase("multiplex",
           lambda a, b: paddle.multiplex(
               [a, b], paddle.to_tensor(np.array([[0], [1], [0], [1]],
                                                 "int32"))),
           lambda a, b: np.stack([a[0], b[1], a[2], b[3]]), [S, S]),
    OpCase("diag", paddle.diag, np.diag, [(4,)]),
    OpCase("trace_op", paddle.trace, np.trace, [(4, 4)]),
    OpCase("block_diag",
           lambda a, b: paddle.block_diag([a, b]),
           lambda a, b: np.block(
               [[a, np.zeros((a.shape[0], b.shape[1]))],
                [np.zeros((b.shape[0], a.shape[1])), b]]), [(2, 3), (3, 2)]),
    OpCase("cartesian_prod",
           lambda a, b: paddle.cartesian_prod([a, b]),
           lambda a, b: np.stack(
               [np.repeat(a, len(b)), np.tile(b, len(a))], 1), [(3,), (4,)]),
    OpCase("diagonal_scatter",
           lambda x, y: paddle.diagonal_scatter(x, y),
           lambda x, y: x - np.diag(np.diag(x)) + np.diag(y),
           [(4, 4), (4,)]),
    OpCase("select_scatter",
           lambda x, y: paddle.select_scatter(x, y, axis=0, index=1),
           lambda x, y: np.concatenate([x[:1], y[None], x[2:]]),
           [S, (5,)]),
    OpCase("slice_scatter",
           lambda x, y: paddle.slice_scatter(x, y, axes=[0], starts=[1],
                                             ends=[3], strides=[1]),
           lambda x, y: np.concatenate([x[:1], y, x[3:]]), [S, (2, 5)]),
    OpCase("index_add",
           lambda x, v: paddle.index_add(x, paddle.to_tensor(_IDX3), 0, v),
           lambda x, v: x + np.add.reduceat(
               np.zeros_like(x), range(len(x)), axis=0) + _index_add_ref(x, v),
           [S, (3, 5)]),
    OpCase("index_fill",
           lambda x: paddle.index_fill(x, paddle.to_tensor(_IDX3), 0, 0.5),
           lambda x: _index_fill_ref(x, 0.5), [S]),
    OpCase("index_put",
           lambda x, v: paddle.index_put(
               x, (paddle.to_tensor(np.array([0, 2], "int64")),), v),
           lambda x, v: _index_put_ref(x, v), [S, (2, 5)]),
    OpCase("put_along_axis",
           lambda x, v: paddle.put_along_axis(
               x, paddle.to_tensor(_IDS[:, :1] % 4), v, 0),
           lambda x, v: _put_along_ref(x, v), [(4, 1), (2, 1)],
           grad_inputs=[0]),
    OpCase("scatter_op",
           lambda x, u: paddle.scatter(
               x, paddle.to_tensor(np.array([1, 3], "int64")), u),
           lambda x, u: _scatter_ref(x, u), [S, (2, 5)]),
    OpCase("scatter_nd_add",
           lambda x, u: paddle.scatter_nd_add(
               x, paddle.to_tensor(np.array([[1], [3]], "int64")), u),
           lambda x, u: _scatter_nd_add_ref(x, u), [S, (2, 5)]),
    OpCase("masked_scatter",
           lambda x, v: paddle.masked_scatter(
               x, paddle.to_tensor(_MASK), v),
           lambda x, v: _masked_scatter_ref(x, v), [S, (20,)]),
    # ---- reductions / search ----------------------------------------------
    OpCase("max", lambda x: paddle.max(x, axis=1), lambda x: x.max(1), [S]),
    OpCase("min", lambda x: paddle.min(x, axis=1), lambda x: x.min(1), [S]),
    OpCase("norm_op", lambda x: paddle.linalg.norm(x, p=2),
           lambda x: np.sqrt((x ** 2).sum()), [S]),
    OpCase("nanmedian", paddle.nanmedian, np.nanmedian, [(9,)], grad=False),
    OpCase("mode_op", lambda x: paddle.mode(paddle.round(x * 2.0))[0],
           lambda x: _mode_ref(np.round(x * 2.0)), [(3, 7)], grad=False,
           dtypes=("float32",)),
    OpCase("cummax_val", lambda x: paddle.cummax(x, axis=1)[0],
           lambda x: np.maximum.accumulate(x, axis=1), [S]),
    OpCase("cummin_val", lambda x: paddle.cummin(x, axis=1)[0],
           lambda x: np.minimum.accumulate(x, axis=1), [S]),
    OpCase("cumulative_trapezoid",
           lambda x: paddle.cumulative_trapezoid(x, axis=1),
           lambda x: np.cumsum((x[:, 1:] + x[:, :-1]) / 2.0, axis=1), [S]),
    # ---- distances / similarity -------------------------------------------
    OpCase("cdist", paddle.cdist,
           lambda x, y: np.sqrt(
               ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)),
           [(4, 3), (5, 3)], grad=False),
    OpCase("pdist", paddle.pdist,
           lambda x: np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1))[
               np.triu_indices(4, 1)], [(4, 3)], grad=False),
    OpCase("dist", lambda x, y: paddle.dist(x, y, p=2),
           lambda x, y: np.sqrt(((x - y) ** 2).sum()), [S, S]),
    OpCase("cosine_similarity",
           lambda x, y: F.cosine_similarity(x, y, axis=1),
           lambda x, y: (x * y).sum(1) / (np.sqrt((x ** 2).sum(1))
                                          * np.sqrt((y ** 2).sum(1))),
           [S, S]),
    OpCase("pairwise_distance",
           lambda x, y: F.pairwise_distance(x, y, p=2.0),
           # reference distance.py adds epsilon to the difference pre-norm
           lambda x, y: np.sqrt((((x - y) + 1e-6) ** 2).sum(-1)), [S, S]),
    OpCase("vecdot", paddle.vecdot,
           lambda x, y: (x * y).sum(-1), [S, S]),
    OpCase("tensordot", lambda x, y: paddle.tensordot(x, y, axes=1),
           lambda x, y: np.tensordot(x, y, axes=1), [(3, 4), (4, 5)]),
    OpCase("renorm", lambda x: paddle.renorm(x, 2.0, 0, 1.0),
           lambda x: x * np.minimum(
               1.0, 1.0 / (np.sqrt((x ** 2).sum(1, keepdims=True)) + 1e-7)),
           [S]),
    OpCase("einsum", lambda x, y: paddle.einsum("ij,jk->ik", x, y),
           lambda x, y: x @ y, [(3, 4), (4, 5)]),
    # ---- losses ------------------------------------------------------------
    OpCase("bce_loss",
           lambda x, y: F.binary_cross_entropy(sps_expit_t(x),
                                               sps_expit_t(y)),
           lambda x, y: -np.mean(
               sps.expit(y) * np.log(sps.expit(x))
               + (1 - sps.expit(y)) * np.log(1 - sps.expit(x))),
           [S, S], grad_inputs=[0]),
    OpCase("huber_loss",
           lambda x, y: _huber_fn(x, y),
           lambda x, y: np.where(
               np.abs(x - y) <= 0.7, 0.5 * (x - y) ** 2,
               0.7 * (np.abs(x - y) - 0.35)).mean(), [S, S]),
    OpCase("hinge_embedding",
           lambda x: F.hinge_embedding_loss(
               x, paddle.to_tensor(_HINGE_LBL)),
           lambda x: np.where(_HINGE_LBL > 0, x,
                              np.maximum(0.0, 1.0 - x)).mean(), [S]),
    OpCase("cosine_embedding",
           lambda a, b: F.cosine_embedding_loss(
               a, b, paddle.to_tensor(_CE_LBL), margin=0.2),
           _cosine_embedding_ref, [S, S]),
    OpCase("margin_ranking",
           lambda a, b: F.margin_ranking_loss(
               a, b, paddle.to_tensor(np.sign(_MASK.astype("float64") - .5)),
               margin=0.1),
           lambda a, b: np.maximum(
               0.0, -np.sign(_MASK - .5) * (a - b) + 0.1).mean(), [S, S]),
    OpCase("multi_label_soft_margin",
           lambda x: F.multi_label_soft_margin_loss(
               x, paddle.to_tensor(_MASK.astype("float32"))),
           lambda x: -np.mean(np.mean(
               _MASK * np.log(sps.expit(x))
               + (1 - _MASK) * np.log(sps.expit(-x)), axis=-1)), [S]),
    OpCase("multi_margin_loss",
           lambda x: F.multi_margin_loss(x, paddle.to_tensor(_LBL4)),
           _multi_margin_ref, [S]),
    OpCase("log_loss_op",
           lambda x, y: F.log_loss(sps_expit_t(x), sps_expit_t(y),
                                   epsilon=1e-4),
           lambda x, y: (-sps.expit(y) * np.log(sps.expit(x) + 1e-4)
                         - (1 - sps.expit(y))
                         * np.log(1 - sps.expit(x) + 1e-4)),
           [S, S], grad_inputs=[0]),
    OpCase("dice_loss_op",
           lambda x: F.dice_loss(sps_expit_t(x),
                                 paddle.to_tensor(_LBL4[:, None])),
           lambda x: _dice_ref(sps.expit(x)), [S]),
    OpCase("triplet_margin",
           lambda a, p, n: F.triplet_margin_loss(a, p, n, margin=1.0),
           # epsilon rides on |a-b| before the p-norm (reference loss.py)
           lambda a, p, n: np.maximum(
               np.sqrt(((np.abs(a - p) + 1e-6) ** 2).sum(-1))
               - np.sqrt(((np.abs(a - n) + 1e-6) ** 2).sum(-1)) + 1.0,
               0.0).mean(),
           [S, S, S], grad=False),
    OpCase("npair_loss",
           lambda a, p: F.npair_loss(a, p, paddle.to_tensor(_LBL4),
                                     l2_reg=0.0),
           _npair_ref, [S, S], grad=False),
    OpCase("gaussian_nll",
           lambda x, y: F.gaussian_nll_loss(x, y, paddle.ones_like(x)),
           lambda x, y: 0.5 * np.mean(np.log(np.maximum(1.0, 1e-6))
                                      + (x - y) ** 2), [S, S]),
    OpCase("nll_loss_op",
           lambda x: F.nll_loss(paddle.log(F.softmax(x, axis=1)),
                                paddle.to_tensor(_LBL4)),
           lambda x: -np.mean(np.log(_np_softmax(x, 1))[np.arange(4), _LBL4]),
           [S]),
    OpCase("label_smooth_op",
           lambda x: F.label_smooth(x, epsilon=0.1),
           lambda x: x * 0.9 + 0.1 / x.shape[-1], [S]),
    OpCase("sigmoid_focal_loss",
           lambda x: F.sigmoid_focal_loss(
               x, paddle.to_tensor(_MASK.astype("float32")),
               reduction="mean"),
           _focal_ref, [S]),
    # ---- norms -------------------------------------------------------------
    OpCase("batch_norm_train",
           lambda x, g, b: _bn_train_fn(x, g, b),
           _bn_ref, [(2, 3, 4, 4), (3,), (3,)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("batch_norm_infer",
           lambda x, g, b: _bn_infer_fn(x, g, b),
           # unit variance still passes through rsqrt(rv + eps)
           lambda x, g, b: x / np.sqrt(1 + 1e-5) * g.reshape(1, -1, 1, 1)
           + b.reshape(1, -1, 1, 1), [(2, 3, 4, 4), (3,), (3,)]),
    OpCase("group_norm_op",
           lambda x, g, b: F.group_norm(x, 2, weight=g, bias=b, epsilon=1e-5),
           _gn_ref, [(2, 4, 3, 3), (4,), (4,)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("instance_norm_op",
           lambda x, g, b: F.instance_norm(x, weight=g, bias=b, eps=1e-5),
           _in_ref, [(2, 3, 4, 4), (3,), (3,)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("rms_norm",
           lambda x, g: _rms_norm_fn(x, g), _rms_norm_ref, [S, (5,)]),
    OpCase("fused_rms_norm",
           lambda x, g: _fused_rms_norm_fn(x, g), _rms_norm_ref, [S, (5,)]),
    OpCase("graph_send_uv", _gsu_fn, _gsu_ref, [(3, 5), (3, 5)]),
    OpCase("fused_linear_cross_entropy", _flce_fn, _flce_ref,
           [(2, 9, 6), (6, 13)],
           # the op fixes fp32 softmax internally; the fp64 numpy reference
           # therefore disagrees past fp32 resolution by design
           fp64=False, rtol=1e-5, atol=1e-5, grad_rtol=1e-2, grad_atol=1e-3),
    OpCase("fused_layer_norm",
           lambda x, g, b: _fused_ln_fn(x, g, b),
           lambda x, g, b: (x - x.mean(-1, keepdims=True))
           / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * g + b,
           [S, (5,), (5,)], grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("lrn_op",
           lambda x: F.local_response_norm(x, size=5),
           _lrn_ref, [(2, 7, 3, 3)], rtol=1e-3, atol=1e-4),
    OpCase("normalize_op",
           lambda x: F.normalize(x, p=2, axis=1),
           lambda x: x / np.sqrt((x ** 2).sum(1, keepdims=True)), [S]),
    # ---- nn primitives -----------------------------------------------------
    OpCase("prelu_op",
           lambda x, w: F.prelu(x, w),
           lambda x, w: np.where(x >= 0, x, x * w.reshape(1, -1, 1, 1)),
           [(2, 3, 4, 4), (3,)], grad_inputs=[1]),
    OpCase("swiglu",
           lambda x, y: F.swiglu(x, y),
           lambda x, y: x * sps.expit(x) * y, [S, S]),
    OpCase("embedding_op",
           lambda w: F.embedding(paddle.to_tensor(_IDS), w),
           lambda w: w[_IDS], [(4, 6)]),
    OpCase("fused_linear",
           lambda x, w, b: paddle.incubate.nn.functional.fused_linear(
               x, w, b),
           lambda x, w, b: x @ w + b, [S, (5, 3), (3,)]),
    OpCase("fused_bias_act",
           lambda x, b: paddle.incubate.nn.functional.fused_bias_act(
               x, b, act_method="gelu"),
           lambda x, b: (x + b) * 0.5
           * (1 + sps.erf((x + b) / np.sqrt(2.0))), [S, (5,)]),
    OpCase("channel_shuffle_op",
           lambda x: F.channel_shuffle(x, 2),
           lambda x: x.reshape(2, 2, 2, 3, 3).transpose(0, 2, 1, 3, 4)
           .reshape(2, 4, 3, 3), [(2, 4, 3, 3)]),
    OpCase("pixel_shuffle_op",
           lambda x: F.pixel_shuffle(x, 2),
           lambda x: x.reshape(2, 1, 2, 2, 3, 3).transpose(0, 1, 4, 2, 5, 3)
           .reshape(2, 1, 6, 6), [(2, 4, 3, 3)]),
    OpCase("pixel_unshuffle_op",
           lambda x: F.pixel_unshuffle(x, 2),
           lambda x: x.reshape(2, 1, 3, 2, 3, 2).transpose(0, 1, 3, 5, 2, 4)
           .reshape(2, 4, 3, 3), [(2, 1, 6, 6)]),
    OpCase("temporal_shift",
           lambda x: F.temporal_shift(x, seg_num=2, shift_ratio=0.25),
           _temporal_shift_ref, [(4, 4, 3, 3)]),
    OpCase("unfold_op",
           lambda x: F.unfold(x, kernel_sizes=2),
           _unfold_ref, [(2, 3, 4, 4)]),
    OpCase("softmax_mask_fuse",
           lambda x: paddle.incubate.softmax_mask_fuse(
               x, paddle.to_tensor(np.zeros((2, 1, 4, 4), "float32"))),
           lambda x: _np_softmax(x, -1), [(2, 2, 4, 4)]),
    OpCase("softmax_mask_fuse_upper_triangle",
           lambda x: paddle.incubate.softmax_mask_fuse_upper_triangle(x),
           _softmax_triu_ref, [(2, 2, 4, 4)]),
    # ---- convs / pools -----------------------------------------------------
    OpCase("conv1d", lambda x, w: F.conv1d(x, w),
           _conv1d_ref, [(2, 3, 6), (4, 3, 3)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("conv2d", lambda x, w: F.conv2d(x, w),
           _conv2d_ref, [(2, 3, 5, 5), (4, 3, 3, 3)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("conv3d", lambda x, w: F.conv3d(x, w),
           _conv3d_ref, [(1, 2, 4, 4, 4), (3, 2, 2, 2, 2)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("conv2d_transpose", lambda x, w: F.conv2d_transpose(x, w),
           _conv2d_transpose_ref, [(2, 3, 4, 4), (3, 4, 3, 3)],
           grad_rtol=2e-2, grad_atol=2e-3),
    OpCase("avg_pool", lambda x: F.avg_pool2d(x, 2),
           _avg_pool2d_ref, [(2, 3, 4, 6)]),
    OpCase("max_pool", lambda x: F.max_pool2d(x, 2),
           _max_pool2d_ref, [(2, 3, 4, 6)]),
    OpCase("adaptive_avg_pool", lambda x: F.adaptive_avg_pool2d(x, 2),
           lambda x: x.reshape(2, 3, 2, 2, 2, 3).mean(axis=(3, 5)),
           [(2, 3, 4, 6)]),
    OpCase("adaptive_max_pool",
           lambda x: F.adaptive_max_pool2d(x, 2),
           lambda x: x.reshape(2, 3, 2, 2, 2, 3).max(axis=(3, 5)),
           [(2, 3, 4, 6)]),
    # ---- interpolate / affine ---------------------------------------------
    OpCase("interpolate_op",
           lambda x: F.interpolate(x, scale_factor=2, mode="nearest"),
           lambda x: x.repeat(2, axis=2).repeat(2, axis=3), [(2, 3, 3, 3)]),
    OpCase("interp_area",
           lambda x: F.interpolate(x, size=(2, 2), mode="area"),
           lambda x: x.reshape(2, 3, 2, 2, 2, 2).mean(axis=(3, 5)),
           [(2, 3, 4, 4)]),
    OpCase("affine_grid",
           lambda t: F.affine_grid(t, [1, 1, 2, 2], align_corners=True),
           _affine_grid_ref, [(1, 2, 3)]),
    # ---- fft (forward vs numpy; complex cotangents are exercised by the
    # jax-level fft tests, FD on complex outputs is ill-posed) ---------------
    OpCase("fft.fft", lambda x: paddle.fft.fft(x).real(),
           lambda x: np.fft.fft(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ifft", lambda x: paddle.fft.ifft(x).real(),
           lambda x: np.fft.ifft(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.fft2", lambda x: paddle.fft.fft2(x).real(),
           lambda x: np.fft.fft2(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ifft2", lambda x: paddle.fft.ifft2(x).real(),
           lambda x: np.fft.ifft2(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.fftn", lambda x: paddle.fft.fftn(x).real(),
           lambda x: np.fft.fftn(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ifftn", lambda x: paddle.fft.ifftn(x).real(),
           lambda x: np.fft.ifftn(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.rfft", lambda x: paddle.fft.rfft(x).real(),
           lambda x: np.fft.rfft(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.irfft", lambda x: paddle.fft.irfft(paddle.complex(x, x)),
           lambda x: np.fft.irfft(x + 1j * x), [S], grad=False, dtypes=("float32",)),
    OpCase("fft.rfft2", lambda x: paddle.fft.rfft2(x).real(),
           lambda x: np.fft.rfft2(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.irfft2", lambda x: paddle.fft.irfft2(paddle.complex(x, x)),
           lambda x: np.fft.irfft2(x + 1j * x), [S], grad=False, dtypes=("float32",)),
    OpCase("fft.rfftn", lambda x: paddle.fft.rfftn(x).real(),
           lambda x: np.fft.rfftn(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.irfftn", lambda x: paddle.fft.irfftn(paddle.complex(x, x)),
           lambda x: np.fft.irfftn(x + 1j * x), [S], grad=False, dtypes=("float32",)),
    OpCase("fft.hfft", lambda x: paddle.fft.hfft(paddle.complex(x, x)),
           lambda x: np.fft.hfft(x + 1j * x), [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ihfft", lambda x: paddle.fft.ihfft(x).real(),
           lambda x: np.fft.ihfft(x).real, [S], grad=False, dtypes=("float32",)),
    OpCase("fft.fftshift", lambda x: paddle.fft.fftshift(x),
           np.fft.fftshift, [S]),
    OpCase("bilinear",
           lambda a, b: F.bilinear(a, b, _get_bilinear_w()),
           lambda a, b: np.einsum("ni,oij,nj->no", a,
                                  _get_bilinear_w().numpy().astype("float64"),
                                  b), [(4, 3), (4, 5)]),
    OpCase("fft.hfft2", lambda x: paddle.fft.hfft2(paddle.complex(x, x)),
           lambda x: np.fft.hfft(np.fft.fft(x + 1j * x, axis=-2), axis=-1),
           [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ihfft2", lambda x: paddle.fft.ihfft2(x).real(),
           lambda x: np.fft.ifft(np.fft.ihfft(x, axis=-1), axis=-2).real,
           [S], grad=False, dtypes=("float32",)),
    OpCase("fft.hfftn", lambda x: paddle.fft.hfftn(paddle.complex(x, x)),
           lambda x: np.fft.hfft(np.fft.fft(x + 1j * x, axis=-2), axis=-1),
           [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ihfftn", lambda x: paddle.fft.ihfftn(x).real(),
           lambda x: np.fft.ifft(np.fft.ihfft(x, axis=-1), axis=-2).real,
           [S], grad=False, dtypes=("float32",)),
    OpCase("fft.ifftshift", lambda x: paddle.fft.ifftshift(x),
           np.fft.ifftshift, [S]),
    # ---- signal / geometric ------------------------------------------------
    OpCase("signal.frame",
           lambda x: paddle.signal.frame(x, frame_length=4, hop_length=2),
           lambda x: _frame_ref(x, 4, 2), [(2, 10)]),
    OpCase("signal.overlap_add",
           lambda x: paddle.signal.overlap_add(x, 2),
           lambda x: _overlap_add_ref(x, 2), [(4, 3)]),
    OpCase("geometric.segment_reduce",
           # count= is the documented jit-capturable form (segment ops need
           # a static segment count inside traced regions)
           lambda x: paddle.geometric.segment_sum(
               x, paddle.to_tensor(np.array([0, 0, 1, 1], "int64")), count=2),
           lambda x: np.stack([x[:2].sum(0), x[2:].sum(0)]), [(4, 3)]),
    OpCase("geometric.send_u_recv",
           lambda x: paddle.geometric.send_u_recv(
               x, paddle.to_tensor(np.array([0, 1, 2], "int64")),
               paddle.to_tensor(np.array([1, 2, 0], "int64")),
               reduce_op="sum"),
           lambda x: np.stack([x[2], x[0], x[1]]), [(3, 4)]),
    OpCase("geometric.send_ue_recv",
           lambda x, e: paddle.geometric.send_ue_recv(
               x, e, paddle.to_tensor(np.array([0, 1, 2], "int64")),
               paddle.to_tensor(np.array([1, 2, 0], "int64")),
               message_op="add", reduce_op="sum"),
           lambda x, e: np.stack([x[2] + e[2], x[0] + e[0], x[1] + e[1]]),
           [(3, 4), (3, 4)]),
    # ---- linalg solvers ----------------------------------------------------
    OpCase("cholesky_solve",
           lambda b: _chol_solve_fn(b), _chol_solve_ref, [(4, 2)]),
    OpCase("cholesky_inverse",
           lambda x: _chol_inverse_fn(x), _chol_inverse_ref, [(4, 4)],
           grad=False),
    OpCase("vision.box_coder",
           lambda d: _box_coder_fn(d), _box_coder_ref, [(3, 4)],
           grad=False, dtypes=("float32",),
           fp64=False),  # prior boxes are f32 constants in the case
    OpCase("rrelu_eval",
           lambda x: F.rrelu(x, lower=0.2, upper=0.4, training=False),
           lambda x: np.where(x >= 0, x, x * 0.3), [S]),
    OpCase("fake_channel_quant_dequant",
           lambda x: _fcqd_fn(x),
           lambda x: np.round(np.clip(x / _chan_scale(x) * 127, -127, 127))
           * _chan_scale(x) / 127, [S], grad=False, dtypes=("float32",),
           fp64=False),  # quant scales are f32-native by design
    OpCase("weight_only_linear",
           lambda x: _wol_fn(x),
           lambda x: x @ (_WOL_Q.astype("float64") * _WOL_S), [S],
           rtol=1e-4, atol=1e-4, dtypes=("float32",),
           fp64=False),  # int8 weight dequant is f32-native by design
]


# ---- waivers ----------------------------------------------------------------
# Every entry must name a registry op and carry the reason it has no OpCase.
WAIVERS = {
    # randomized outputs: no deterministic numpy oracle (distribution-level
    # checks live in the dedicated suites)
    "dropout_op": "random mask; distributional checks in test_nn dropout",
    "dropout_axis": "random mask (axis variant)",
    "alpha_dropout_op": "random mask; mean/var checks in test_nn",
    "rrelu_train": "random slopes; eval path has an OpCase",
    "gumbel_softmax_inner": "random gumbel noise; tested in test_nn",
    "gamma": "random sampling op (distribution tests cover moments)",
    "fused_dropout_add": "random mask; composition tested in test_models",
    "fused_gate_attention": "10-input einsum composite; fp64 oracle parity "
                            "(merged/unmerged, gating, both biases) in "
                            "test_fused_functional.TestFusedGateAttention",
    # decompositions: outputs unique only up to sign/permutation — direct
    # numpy comparison is ill-posed; reconstruction tests live in
    # test_misc_kits linalg
    "eigh": "sign-ambiguous eigenvectors; reconstruction-tested",
    "qr": "sign-ambiguous factors; reconstruction-tested",
    "svd": "sign-ambiguous factors; reconstruction-tested",
    "householder_product": "composition of reflectors; covered via qr tests",
    # attention kernels: dedicated correctness suites (tests/test_pallas.py;
    # on the chip, chip_smoke.py's flash phase)
    "flash_attention": "vs math-path oracle in test_pallas + chip_smoke on-device",
    "flash_attn_varlen": "vs dense-attention oracle in test_nn varlen tests",
    # recurrent/scan kernels: sequence-level tests in test_nn rnn suites
    "rnn_scan": "lstm/gru sequence parity tests in test_nn",
    "gru_cell": "cell-level parity tests in test_nn",
    "simple_rnn_cell": "cell drives the rnn_scan sequence suites; torch "
                       "gate-order parity in test_torch_parity",
    "lstm_cell": "cell drives the rnn_scan sequence suites; torch "
                 "gate-order parity in test_torch_parity",
    "ctc_loss_op": "forward-algorithm lattice; torch parity in "
                   "test_torch_parity test_ctc_loss_matches_torch",
    "rnnt_loss": "lattice recursion tested against slow DP in test_nn",
    # kernels with dedicated suites where a flat numpy oracle would just
    # duplicate a weaker copy of the existing test
    "margin_cross_entropy": "mp-aware loss; tested in test_fleet mpu",
    "hsigmoid_loss": "huffman-tree paths; tested in test_nn",
    "vision.deform_conv2d": "tested against torchvision formula in test_vision_hapi",
    "vision.roi_align": "tested in test_vision_hapi",
    "grid_sample": "bilinear sampling tested in test_vision_hapi",
    "max_unpool2d_inner": "pool/unpool roundtrip tested in test_nn",
    "as_strided": "view mechanics tested in test_tensor",
    "setitem": "in-place indexing tested in test_tensor",
    "fake_quant_dequant": "QAT roundtrip tested in test_misc_kits quantization",
    "fold_op": "inverse-of-unfold roundtrip tested in test_nn",
    "conv3d_transpose_inner": "3d transpose tested via Conv3DTranspose in test_nn",
    "fused_rotary_position_embedding": "rotation parity tested in test_models rope tests",
}


_TAIL_BY_NAME = {c.name: c for c in TAIL_CASES}


@pytest.mark.parametrize("name", sorted(_TAIL_BY_NAME), ids=str)
def test_forward(name):
    _TAIL_BY_NAME[name].run_forward()


_GRAD = sorted(n for n, c in _TAIL_BY_NAME.items() if c.grad)


@pytest.mark.parametrize("name", _GRAD, ids=str)
def test_grad_finite_difference(name):
    _TAIL_BY_NAME[name].run_grad()


_STATIC_CASES = sorted(n for n, c in _TAIL_BY_NAME.items() if c.static)


@pytest.mark.parametrize("name", _STATIC_CASES, ids=str)
def test_static_consistency(name):
    """Every op through jit capture + the static Executor (VERDICT r4 #5;
    reference op_test.py:418 dygraph/static/PIR consistency)."""
    _TAIL_BY_NAME[name].run_static()


def test_static_waivers_bounded():
    """GLOBAL bound across both registry files — per-file bounds would let
    the repo-wide count silently reach 2x the budget."""
    import test_ops_numeric as base_mod

    all_cases = {**base_mod._BY_NAME, **_TAIL_BY_NAME}
    waived = sorted(n for n, c in all_cases.items() if not c.static)
    assert len(waived) < 5, (
        "static-consistency waivers must stay below 5 repo-wide "
        "(VERDICT r4 #5): "
        f"{[(n, all_cases[n].static_waiver) for n in waived]}")


class TestCoverageEnforcement:
    """The registry is the source of truth: a differentiable op with neither
    an OpCase nor a waiver fails CI (legacy_test/op_test.py discipline)."""

    def _covered(self):
        import test_ops_numeric as base

        return set(base._BY_NAME) | set(_TAIL_BY_NAME)

    def test_every_differentiable_op_has_case_or_waiver(self):
        from paddle_tpu.ops.optable import op_table

        diff = {r["name"] for r in op_table() if r["differentiable"]}
        missing = sorted(diff - self._covered() - set(WAIVERS))
        assert not missing, (
            f"{len(missing)} differentiable op(s) have neither an OpCase nor "
            f"a waiver: {missing}")

    def test_waiver_list_bounded(self):
        assert len(WAIVERS) < 40, "waiver list must stay below 40 (verdict #6)"

    def test_no_stale_waivers(self):
        from paddle_tpu.ops.optable import op_table

        names = {r["name"] for r in op_table()}
        covered = self._covered()
        unknown = sorted(w for w in WAIVERS if w not in names)
        assert not unknown, f"waivers for unknown ops: {unknown}"
        stale = sorted(w for w in WAIVERS if w in covered)
        assert not stale, f"waived ops that now have OpCases: {stale}"
