"""Numeric sweep — the remaining api.yaml forward ops (VERDICT r2 #5).

Closes the numeric-test tail: every op here was resolvable but not yet
numerically exercised by test_ops.py or the three earlier sweeps. Pattern
follows the reference OpTest culture (op_test.py:289): independent numpy/
scipy references for values, central-difference vs tape for gradients;
random ops get statistical checks, structured ops (roi/deform/viterbi)
get exactness special cases plus brute-force references.

tests/numeric_coverage.py records the full op -> test-file partition;
tests/test_op_coverage.py asserts it is total.
"""
import numpy as np
import pytest
import scipy.special as sps

import paddle_tpu as paddle
from op_test import check_grad, check_output

F = paddle.nn.functional


def t(a):
    return paddle.to_tensor(a)


def _rand(shape, lo=-1.0, hi=1.0, seed=0):
    rng = np.random.RandomState(seed)
    return (lo + (hi - lo) * rng.rand(*shape)).astype(np.float32)


# ---------------------------------------------------------------- unary ----

UNARY = [
    ("acos", paddle.acos, np.arccos, _rand((2, 3), -0.9, 0.9), True),
    ("sinh", paddle.sinh, np.sinh, _rand((2, 3), -2, 2), True),
    ("erf", paddle.erf, sps.erf, _rand((2, 3), -2, 2), True),
    ("lgamma", paddle.lgamma, sps.gammaln, _rand((2, 3), 0.5, 4.0), True),
    ("log1p", paddle.log1p, np.log1p, _rand((2, 3), -0.5, 2.0), True),
    ("round", paddle.round, np.round, _rand((2, 3), -3, 3), False),
]


@pytest.mark.parametrize("name,fn,ref,x,diff", UNARY,
                         ids=[u[0] for u in UNARY])
def test_unary_rest(name, fn, ref, x, diff):
    check_output(fn, ref, [x], rtol=2e-5, atol=2e-5)
    if diff:
        check_grad(fn, [x.astype(np.float64)])


def test_clip_scale():
    x = _rand((3, 4), -2, 2)
    check_output(lambda a: paddle.clip(a, -0.5, 0.8),
                 lambda a: np.clip(a, -0.5, 0.8), [x])
    check_grad(lambda a: paddle.clip(a, -0.5, 0.8),
               [x.astype(np.float64)])
    check_output(lambda a: paddle.scale(a, scale=2.5, bias=0.5),
                 lambda a: 2.5 * a + 0.5, [x])
    check_output(
        lambda a: paddle.scale(a, scale=2.5, bias=0.5,
                               bias_after_scale=False),
        lambda a: 2.5 * (a + 0.5), [x])


def test_complex_parts():
    z = (_rand((2, 3)) + 1j * _rand((2, 3), seed=1)).astype(np.complex64)
    check_output(paddle.real, np.real, [z])
    check_output(paddle.imag, np.imag, [z])
    check_output(paddle.conj, np.conj, [z])


def test_isfinite_allclose():
    x = np.array([[1.0, np.inf], [np.nan, -2.0]], np.float32)
    check_output(paddle.isfinite, np.isfinite, [x])
    a = _rand((2, 3))
    b = a + 1e-9
    assert bool(paddle.allclose(t(a), t(b)))
    assert not bool(paddle.allclose(t(a), t(a + 1.0)))


def test_bitwise():
    rng = np.random.RandomState(0)
    a = rng.randint(0, 16, (3, 4)).astype(np.int32)
    b = rng.randint(0, 16, (3, 4)).astype(np.int32)
    check_output(paddle.bitwise_and, np.bitwise_and, [a, b])
    check_output(paddle.bitwise_or, np.bitwise_or, [a, b])
    check_output(paddle.bitwise_xor, np.bitwise_xor, [a, b])


def test_all_any_add_n():
    m = np.array([[True, False], [True, True]])
    check_output(paddle.all, np.all, [m])
    check_output(lambda a: paddle.all(a, axis=1),
                 lambda a: a.all(1), [m])
    check_output(paddle.any, np.any, [m])
    xs = [_rand((2, 3), seed=s) for s in range(3)]
    out = paddle.add_n([t(x) for x in xs])
    np.testing.assert_allclose(out.numpy(), sum(xs), rtol=1e-6)


def test_softmax_log_softmax_grad():
    x = _rand((3, 5), -2, 2)

    def np_softmax(v, axis=-1):
        e = np.exp(v - v.max(axis, keepdims=True))
        return e / e.sum(axis, keepdims=True)

    check_output(F.softmax, np_softmax, [x])
    check_output(F.log_softmax, lambda v: np.log(np_softmax(v)), [x])
    check_grad(F.log_softmax, [x.astype(np.float64)])


def test_cast():
    x = _rand((2, 3), -2, 2)
    check_output(lambda a: paddle.cast(a, "int32"),
                 lambda a: a.astype(np.int32), [x])
    check_output(lambda a: paddle.cast(a, "float64"),
                 lambda a: a.astype(np.float64), [x])


# ------------------------------------------------------------- creation ----

def test_creation_ops():
    np.testing.assert_array_equal(paddle.arange(2, 14, 3).numpy(),
                                  np.arange(2, 14, 3))
    np.testing.assert_array_equal(paddle.eye(3, 5).numpy(), np.eye(3, 5))
    np.testing.assert_allclose(paddle.linspace(0, 1, 7).numpy(),
                               np.linspace(0, 1, 7), rtol=1e-6)
    np.testing.assert_array_equal(
        paddle.full([2, 3], 7.5).numpy(), np.full((2, 3), 7.5, np.float32))
    v = _rand((4,))
    np.testing.assert_array_equal(paddle.diag(t(v)).numpy(), np.diag(v))
    m = _rand((3, 3))
    np.testing.assert_array_equal(paddle.diag(t(m)).numpy(), np.diag(m))
    x = _rand((2, 3))
    np.testing.assert_array_equal(paddle.ones_like(t(x)).numpy(),
                                  np.ones_like(x))
    np.testing.assert_array_equal(paddle.zeros_like(t(x)).numpy(),
                                  np.zeros_like(x))


def test_shape_size_is_empty_copy_to():
    x = t(_rand((2, 3, 4)))
    np.testing.assert_array_equal(np.asarray(paddle.shape(x)), [2, 3, 4])
    assert int(paddle.numel(x)) == 24
    assert not bool(paddle.is_empty(x))
    assert bool(paddle.is_empty(t(np.zeros((0, 3), np.float32))))
    # a device-placement copy must preserve values (Tensor.cuda/.tpu name
    # the accelerator and raise on a CPU-only host: tests/test_chip_rules.py)
    y = x.to(paddle.get_device())
    np.testing.assert_array_equal(y.numpy(), x.numpy())


# --------------------------------------------------------- manipulation ----

def test_manipulation_values():
    x = _rand((2, 3, 4))
    check_output(lambda a: paddle.concat([a, a], axis=1),
                 lambda a: np.concatenate([a, a], 1), [x])
    check_output(lambda a: paddle.expand(a, [2, 2, 3, 4]),
                 lambda a: np.broadcast_to(a, (2, 2, 3, 4)), [x])
    check_output(lambda a: paddle.flatten(a, 1, 2),
                 lambda a: a.reshape(2, 12), [x])
    check_output(lambda a: paddle.reshape(a, [4, 6]),
                 lambda a: a.reshape(4, 6), [x])
    check_output(lambda a: paddle.roll(a, 2, axis=1),
                 lambda a: np.roll(a, 2, 1), [x])
    check_output(lambda a: paddle.slice(a, [1, 2], [1, 0], [3, 2]),
                 lambda a: a[:, 1:3, 0:2], [x])
    outs = paddle.split(t(x), 3, axis=1)
    for o, e in zip(outs, np.split(x, 3, 1)):
        np.testing.assert_array_equal(o.numpy(), e)
    check_output(lambda a: paddle.squeeze(paddle.unsqueeze(a, 0), 0),
                 lambda a: a, [x])
    check_output(lambda a: paddle.stack([a, a], axis=1),
                 lambda a: np.stack([a, a], 1), [x])
    check_output(lambda a: paddle.tile(a, [1, 2, 1]),
                 lambda a: np.tile(a, (1, 2, 1)), [x])
    check_output(lambda a: paddle.transpose(a, [2, 0, 1]),
                 lambda a: a.transpose(2, 0, 1), [x])
    check_grad(lambda a: paddle.transpose(a, [2, 0, 1]),
               [x.astype(np.float64)])


def test_gather_scatter_family():
    x = _rand((5, 4))
    idx = np.array([3, 1, 4], np.int64)
    check_output(lambda a: paddle.gather(a, t(idx)),
                 lambda a: a[idx], [x])
    check_grad(lambda a: paddle.gather(a, t(idx)), [x.astype(np.float64)])
    upd = _rand((3, 4), seed=2)
    ref = x.copy()
    ref[idx] = upd
    out = paddle.scatter(t(x), t(idx), t(upd), overwrite=True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-6)
    tk = _rand((4, 6))
    ti = np.array([[1, 0], [2, 3], [4, 5], [0, 1]], np.int64)
    check_output(lambda a: paddle.take_along_axis(a, t(ti), 1),
                 lambda a: np.take_along_axis(a, ti, 1), [tk])
    mask = x > 0
    np.testing.assert_array_equal(
        paddle.masked_select(t(x), t(mask)).numpy(), x[mask])
    cond = x > 0
    y = _rand((5, 4), seed=3)
    check_output(lambda a, b: paddle.where(t(cond), a, b),
                 lambda a, b: np.where(cond, a, b), [x, y])
    nz = paddle.nonzero(t(cond)).numpy()
    np.testing.assert_array_equal(nz, np.argwhere(cond))


def test_topk_tril_triu_unfold():
    x = _rand((3, 6))
    vals, idxs = paddle.topk(t(x), k=2, axis=1)
    ref_idx = np.argsort(-x, axis=1)[:, :2]
    np.testing.assert_array_equal(np.sort(idxs.numpy(), 1),
                                  np.sort(ref_idx, 1))
    np.testing.assert_allclose(np.sort(vals.numpy(), 1),
                               np.sort(np.take_along_axis(x, ref_idx, 1), 1),
                               rtol=1e-6)
    m = _rand((4, 4))
    check_output(paddle.tril, np.tril, [m])
    check_output(paddle.triu, np.triu, [m])
    # unfold (im2col): reference layout [N, C*kh*kw, L]
    img = _rand((1, 2, 4, 4))
    out = F.unfold(t(img), kernel_sizes=2).numpy()
    assert out.shape == (1, 2 * 2 * 2, 9)
    # first column = the top-left 2x2 patch of each channel, row-major
    patch = img[0, :, :2, :2].reshape(2, 4)
    np.testing.assert_allclose(out[0, :, 0], patch.reshape(-1), rtol=1e-6)


# ---------------------------------------------------------------- random ----

def test_randint_truncated_normal_stats():
    paddle.seed(1234)
    r = paddle.randint(3, 9, [2000]).numpy()
    assert r.min() >= 3 and r.max() <= 8
    assert set(np.unique(r)) == set(range(3, 9))
    g = paddle.nn.initializer.TruncatedNormal(mean=0.0, std=1.0)
    vals = np.asarray(g([4000], "float32"))
    assert np.abs(vals).max() <= 2.0 + 1e-6  # truncation at 2 std
    assert abs(vals.mean()) < 0.1


# ---------------------------------------------------------------- linalg ----

def test_linalg_rest():
    rng = np.random.RandomState(0)
    a = rng.randn(4, 4).astype(np.float32)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    L = paddle.linalg.cholesky(t(spd)).numpy()
    np.testing.assert_allclose(L @ L.T, spd, rtol=1e-4, atol=1e-4)
    b = rng.randn(4, 2).astype(np.float32)
    x = paddle.linalg.cholesky_solve(t(b), t(np.linalg.cholesky(spd)),
                                     upper=False).numpy()
    np.testing.assert_allclose(spd @ x, b, rtol=1e-3, atol=1e-3)
    check_output(paddle.linalg.det, np.linalg.det, [spd], rtol=1e-4,
                 atol=1e-4)
    ms = [rng.randn(3, 4).astype(np.float32),
          rng.randn(4, 5).astype(np.float32),
          rng.randn(5, 2).astype(np.float32)]
    np.testing.assert_allclose(
        paddle.linalg.multi_dot([t(m) for m in ms]).numpy(),
        ms[0] @ ms[1] @ ms[2], rtol=1e-4)
    v = rng.randn(4).astype(np.float32)
    np.testing.assert_allclose(paddle.mv(t(a), t(v)).numpy(), a @ v,
                               rtol=1e-5)
    q, r = paddle.linalg.qr(t(a))
    np.testing.assert_allclose(q.numpy() @ r.numpy(), a, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(q.numpy().T @ q.numpy(), np.eye(4),
                               atol=1e-4)


# ----------------------------------------------------- nn/vision/special ----

def test_prelu():
    x = _rand((2, 3, 4), -2, 2)
    w = np.array([0.25, 0.1, 0.5], np.float32)
    check_output(lambda a, ww: F.prelu(a, ww),
                 lambda a, ww: np.where(a > 0, a, a * ww.reshape(1, 3, 1)),
                 [x, w])


def test_max_pool3d_with_index():
    x = _rand((1, 1, 4, 4, 4))
    out, mask = F.max_pool3d(t(x), kernel_size=2, stride=2,
                             return_mask=True)
    ref = x.reshape(1, 1, 2, 2, 2, 2, 2, 2).transpose(
        0, 1, 2, 4, 6, 3, 5, 7).reshape(1, 1, 2, 2, 2, 8).max(-1)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-6)
    # indices are flat positions within the input volume; re-gathering must
    # reproduce the pooled values
    flat = x.reshape(1, 1, -1)
    np.testing.assert_allclose(
        np.take_along_axis(flat, mask.numpy().reshape(1, 1, -1), 2).reshape(
            out.shape), out.numpy(), rtol=1e-6)


def test_deform_conv_zero_offset_equals_conv():
    from paddle_tpu.vision.ops import deform_conv2d

    x = _rand((1, 2, 6, 6))
    w = _rand((3, 2, 3, 3), seed=1)
    offset = np.zeros((1, 2 * 3 * 3, 4, 4), np.float32)
    out = deform_conv2d(t(x), t(offset), t(w)).numpy()
    ref = F.conv2d(t(x), t(w)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_roi_align_identity_and_grad():
    from paddle_tpu.vision.ops import roi_align

    x = _rand((1, 1, 4, 4))
    # exactness case: aligned=True shifts by -0.5, so a full-image box with
    # output HxW and sampling_ratio=1 samples exactly at the pixel centers
    # (xs = -0.5 + (ix + 0.5) * 1 = ix) -> identity
    boxes = np.array([[0.0, 0.0, 4.0, 4.0]], np.float32)
    out = roi_align(t(x), t(boxes), t(np.array([1], np.int32)),
                    output_size=4, sampling_ratio=1, aligned=True).numpy()
    np.testing.assert_allclose(out[0, 0], x[0, 0], rtol=1e-5, atol=1e-5)


def test_roi_pool_per_pixel_bins():
    from paddle_tpu.vision.ops import roi_pool

    # exactness case: full-image box with output HxW makes every quantized
    # bin one pixel (ys = iy + frac, int -> iy) -> identity
    x = _rand((1, 2, 6, 6))
    boxes = np.array([[0.0, 0.0, 5.0, 5.0]], np.float32)
    out = roi_pool(t(x), t(boxes), t(np.array([1], np.int32)),
                   output_size=6).numpy()
    np.testing.assert_allclose(out[0], x[0], rtol=1e-6)


def test_psroi_pool_constant():
    from paddle_tpu.vision.ops import psroi_pool

    # position-sensitive pooling of a constant input returns the constant
    oh = ow = 2
    c = 3
    x = np.full((1, oh * ow * c, 6, 6), 2.5, np.float32)
    boxes = np.array([[0.0, 0.0, 5.0, 5.0]], np.float32)
    out = psroi_pool(t(x), t(boxes), t(np.array([1], np.int32)),
                     output_size=oh).numpy()
    assert out.shape == (1, c, oh, ow)
    np.testing.assert_allclose(out, np.full((1, c, oh, ow), 2.5), rtol=1e-6)


def test_yolo_box_numpy_ref():
    from paddle_tpu.vision.ops import yolo_box

    rng = np.random.RandomState(0)
    class_num, na, H, W = 2, 2, 3, 3
    anchors = [10, 14, 23, 27]
    xin = rng.randn(1, na * (5 + class_num), H, W).astype(np.float32)
    img = np.array([[96, 96]], np.int32)
    boxes, scores = yolo_box(t(xin), t(img), anchors, class_num,
                             conf_thresh=0.0, downsample_ratio=32,
                             clip_bbox=False)
    a = xin.reshape(1, na, 5 + class_num, H, W)
    an = np.array(anchors, np.float32).reshape(na, 2)
    sig = lambda v: 1 / (1 + np.exp(-v))
    gx = np.arange(W)[None, None, None, :]
    gy = np.arange(H)[None, None, :, None]
    bx = (gx + sig(a[:, :, 0])) / W
    by = (gy + sig(a[:, :, 1])) / H
    bw = np.exp(a[:, :, 2]) * an[None, :, 0:1, None] / (W * 32)
    bh = np.exp(a[:, :, 3]) * an[None, :, 1:2, None] / (H * 32)
    x1 = (bx - bw / 2) * 96
    y1 = (by - bh / 2) * 96
    x2 = (bx + bw / 2) * 96
    y2 = (by + bh / 2) * 96
    ref_boxes = np.stack([x1, y1, x2, y2], -1).reshape(1, -1, 4)
    np.testing.assert_allclose(boxes.numpy(), ref_boxes, rtol=1e-4,
                               atol=1e-4)
    conf = sig(a[:, :, 4])
    probs = sig(a[:, :, 5:]) * conf[:, :, None]
    ref_scores = probs.transpose(0, 1, 3, 4, 2).reshape(1, -1, class_num)
    np.testing.assert_allclose(scores.numpy(), ref_scores, rtol=1e-4,
                               atol=1e-4)


def test_gather_tree():
    # [max_time, batch, beam] ids + parents; backtrace from last step
    ids = np.array([[[2, 5]], [[6, 8]], [[3, 9]]], np.int64)
    parents = np.array([[[0, 0]], [[1, 0]], [[0, 1]]], np.int64)
    out = F.gather_tree(t(ids), t(parents)).numpy()
    # beam 0 at t=2 -> parent 0 at t=1 (id 6, parent 1) -> t=0 id 5
    # beam 1 at t=2 -> parent 1 at t=1 (id 8, parent 0) -> t=0 id 2
    ref = np.array([[[5, 2]], [[6, 8]], [[3, 9]]], np.int64)
    np.testing.assert_array_equal(out, ref)


def test_graph_send_recv_and_segment_pool():
    from paddle_tpu.incubate import graph_send_recv, segment_mean, \
        segment_sum

    x = _rand((5, 3))
    src = np.array([0, 1, 2, 3], np.int64)
    dst = np.array([1, 1, 0, 4], np.int64)
    out = graph_send_recv(t(x), t(src), t(dst), pool_type="sum").numpy()
    ref = np.zeros_like(x)
    for s, d in zip(src, dst):
        ref[d] += x[s]
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    ids = np.array([0, 0, 1, 2, 2], np.int64)
    np.testing.assert_allclose(
        segment_sum(t(x), t(ids)).numpy(),
        np.stack([x[:2].sum(0), x[2], x[3:].sum(0)]), rtol=1e-6)
    np.testing.assert_allclose(
        segment_mean(t(x), t(ids)).numpy(),
        np.stack([x[:2].mean(0), x[2], x[3:].mean(0)]), rtol=1e-6)


def test_viterbi_decode_bruteforce():
    from paddle_tpu.text import viterbi_decode

    rng = np.random.RandomState(0)
    B, T, K = 2, 4, 3
    pot = rng.randn(B, T, K).astype(np.float32)
    trans = rng.randn(K, K).astype(np.float32)
    lengths = np.array([4, 3], np.int64)
    scores, paths = viterbi_decode(t(pot), t(trans), t(lengths),
                                   include_bos_eos_tag=False)
    import itertools

    for b in range(B):
        L = int(lengths[b])
        best, best_path = -1e30, None
        for path in itertools.product(range(K), repeat=L):
            s = pot[b, 0, path[0]]
            for i in range(1, L):
                s += trans[path[i - 1], path[i]] + pot[b, i, path[i]]
            if s > best:
                best, best_path = s, path
        np.testing.assert_allclose(float(scores.numpy()[b]), best,
                                   rtol=1e-4)
        np.testing.assert_array_equal(paths.numpy()[b, :L], best_path)


# ---------------------------------------------------------------- metric ----

def test_accuracy_and_auc():
    probs = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7], [0.6, 0.4]],
                     np.float32)
    labels = np.array([[1], [0], [0], [1]], np.int64)
    acc = paddle.metric.accuracy(t(probs), t(labels), k=1)
    np.testing.assert_allclose(float(acc), 0.5)  # rows 0,1 right; 2,3 wrong

    m = paddle.metric.Auc()
    m.update(probs, labels)
    # rank-based AUC over pos scores [0.9, 0.4], neg scores [0.2, 0.7]
    pos, neg = [0.9, 0.4], [0.2, 0.7]
    pairs = [(p > n) + 0.5 * (p == n) for p in pos for n in neg]
    np.testing.assert_allclose(m.accumulate(), np.mean(pairs), atol=1e-3)


# ------------------------------------------------------------ optimizers ----

def _one_step(opt_cls, np_update, seed=0, **opt_kw):
    """Run ONE optimizer step on a known gradient and compare against the
    reference update formula in numpy (reference OpTest for sgd/adam/...)."""
    rng = np.random.RandomState(seed)
    w0 = rng.randn(4, 3).astype(np.float32)
    g = rng.randn(4, 3).astype(np.float32)
    p = paddle.to_tensor(w0.copy())
    p.stop_gradient = False
    opt = opt_cls(parameters=[p], **opt_kw)
    (p * t(g)).sum().backward()
    opt.step()
    ref = np_update(w0, g)
    np.testing.assert_allclose(p.numpy(), ref, rtol=1e-5, atol=1e-6)


def test_sgd_step():
    _one_step(paddle.optimizer.SGD, lambda w, g: w - 0.1 * g,
              learning_rate=0.1)


def test_momentum_step():
    # velocity = mu*0 + g; w -= lr * velocity
    _one_step(paddle.optimizer.Momentum, lambda w, g: w - 0.1 * g,
              learning_rate=0.1, momentum=0.9)


def _adam_ref(w, g, lr=0.01, b1=0.9, b2=0.999, eps=1e-8, wd=0.0):
    m = (1 - b1) * g
    v = (1 - b2) * g * g
    mhat = m / (1 - b1)
    vhat = v / (1 - b2)
    out = w - lr * mhat / (np.sqrt(vhat) + eps)
    if wd:
        out = out - lr * wd * w
    return out


def test_adam_step():
    _one_step(paddle.optimizer.Adam, lambda w, g: _adam_ref(w, g),
              learning_rate=0.01)


def test_adamw_step():
    _one_step(paddle.optimizer.AdamW,
              lambda w, g: _adam_ref(w, g, wd=0.05),
              learning_rate=0.01, weight_decay=0.05)


def test_adamax_step():
    def ref(w, g, lr=0.01, b1=0.9, b2=0.999, eps=1e-8):
        m = (1 - b1) * g
        u = np.maximum(0.0, np.abs(g))  # inf-norm accumulator
        return w - lr / (1 - b1) * m / (u + eps)

    _one_step(paddle.optimizer.Adamax, ref, learning_rate=0.01)


def test_adadelta_step():
    def ref(w, g, rho=0.95, eps=1e-6, lr=1.0):
        acc = (1 - rho) * g * g
        upd = np.sqrt(eps) / np.sqrt(acc + eps) * g
        return w - lr * upd

    _one_step(paddle.optimizer.Adadelta, ref, learning_rate=1.0,
              rho=0.95, epsilon=1e-6)
