"""Single-op tests vs numpy for the north-star op set (SURVEY.md §7 stage 3).
Mirrors the reference's test_matmul_op.py / test_softmax_op.py / ... pattern."""
import numpy as np
import pytest

from op_test import OpTest


class TestMatmul(OpTest):
    op_type = "matmul"

    def test_basic(self):
        self.setup()
        x = np.random.rand(4, 8).astype(np.float64)
        y = np.random.rand(8, 5).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x @ y}
        self.check_output()
        self.check_grad(["X", "Y"], "Out")

    def test_transpose(self):
        self.setup()
        x = np.random.rand(8, 4).astype(np.float64)
        y = np.random.rand(5, 8).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"transpose_X": True, "transpose_Y": True, "alpha": 2.0}
        self.outputs = {"Out": 2.0 * (x.T @ y.T)}
        self.check_output()
        self.check_grad(["X", "Y"], "Out")

    def test_batched(self):
        self.setup()
        x = np.random.rand(3, 4, 8).astype(np.float64)
        y = np.random.rand(3, 8, 5).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": np.matmul(x, y)}
        self.check_output()


class TestMul(OpTest):
    op_type = "mul"

    def test_basic(self):
        self.setup()
        x = np.random.rand(4, 2, 3).astype(np.float64)
        y = np.random.rand(6, 5).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x.reshape(4, 6) @ y}
        self.check_output()
        self.check_grad(["X", "Y"], "Out")


class TestElementwise(OpTest):
    def _run(self, op, fn, grad=True):
        self.op_type = op
        self.setup()
        x = np.random.rand(3, 4).astype(np.float64) + 0.5
        y = np.random.rand(3, 4).astype(np.float64) + 0.5
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": fn(x, y)}
        self.check_output()
        if grad:
            self.check_grad(["X", "Y"], "Out")

    def test_add(self):
        self._run("elementwise_add", np.add)

    def test_sub(self):
        self._run("elementwise_sub", np.subtract)

    def test_mul(self):
        self._run("elementwise_mul", np.multiply)

    def test_div(self):
        self._run("elementwise_div", np.divide)

    def test_max(self):
        self._run("elementwise_max", np.maximum, grad=False)

    def test_min(self):
        self._run("elementwise_min", np.minimum, grad=False)

    def test_pow(self):
        self._run("elementwise_pow", np.power)

    def test_broadcast_axis(self):
        self.op_type = "elementwise_add"
        self.setup()
        x = np.random.rand(2, 3, 4, 5).astype(np.float64)
        y = np.random.rand(3, 4).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": x + y.reshape(1, 3, 4, 1)}
        self.check_output()


class TestActivations(OpTest):
    def _run(self, op, fn, grad=True, x=None):
        self.op_type = op
        self.setup()
        if x is None:
            x = np.random.rand(3, 7).astype(np.float64) + 0.25
        self.inputs = {"X": x}
        self.outputs = {"Out": fn(x)}
        self.check_output()
        if grad:
            self.check_grad(["X"], "Out")

    def test_relu(self):
        x = np.random.randn(3, 7).astype(np.float64)
        x[np.abs(x) < 0.05] = 0.5
        self._run("relu", lambda v: np.maximum(v, 0), x=x)

    def test_sigmoid(self):
        self._run("sigmoid", lambda v: 1 / (1 + np.exp(-v)))

    def test_tanh(self):
        self._run("tanh", np.tanh)

    def test_exp(self):
        self._run("exp", np.exp)

    def test_log(self):
        self._run("log", np.log)

    def test_sqrt(self):
        self._run("sqrt", np.sqrt)

    def test_square(self):
        self._run("square", np.square)

    def test_gelu(self):
        from scipy.special import erf
        self._run("gelu", lambda v: 0.5 * v * (1 + erf(v / np.sqrt(2))))

    def test_abs(self):
        self._run("abs", np.abs)


class TestReduce(OpTest):
    def _run(self, op, fn, attrs, expected=None, grad=True):
        self.op_type = op
        self.setup()
        x = np.random.rand(2, 3, 4).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = attrs
        self.outputs = {"Out": fn(x) if expected is None else expected}
        self.check_output()
        if grad:
            self.check_grad(["X"], "Out")

    def test_sum_all(self):
        self._run("reduce_sum", lambda x: x.sum(), {"reduce_all": True})

    def test_sum_dim(self):
        self._run("reduce_sum", lambda x: x.sum(axis=1), {"dim": [1]})

    def test_mean_keepdim(self):
        self._run("reduce_mean", lambda x: x.mean(axis=(0, 2), keepdims=True),
                  {"dim": [0, 2], "keep_dim": True})

    def test_max(self):
        self._run("reduce_max", lambda x: x.max(axis=2), {"dim": [2]},
                  grad=False)

    def test_prod(self):
        self._run("reduce_prod", lambda x: x.prod(axis=0), {"dim": [0]})


class TestSoftmax(OpTest):
    op_type = "softmax"

    def test_basic(self):
        self.setup()
        x = np.random.rand(3, 10).astype(np.float64)
        e = np.exp(x - x.max(-1, keepdims=True))
        self.inputs = {"X": x}
        self.outputs = {"Out": e / e.sum(-1, keepdims=True)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestSoftmaxWithCrossEntropy(OpTest):
    op_type = "softmax_with_cross_entropy"

    def test_hard_label(self):
        self.setup()
        logits = np.random.rand(5, 7).astype(np.float64)
        label = np.random.randint(0, 7, (5, 1)).astype(np.int64)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(5), label.ravel()]).reshape(5, 1)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss}
        self.check_output()
        self.check_grad(["Logits"], "Loss")

    def test_ignore_index(self):
        """Ignored rows lose nothing and get no gradient, whatever the
        index's sign (here a valid class id)."""
        self.setup()
        logits = np.random.rand(8, 7).astype(np.float64)
        label = np.random.randint(0, 7, (8, 1)).astype(np.int64)
        label[::4] = 3
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(8), label.ravel()]).reshape(8, 1)
        loss[label == 3] = 0.0
        self.inputs = {"Logits": logits, "Label": label}
        self.attrs = {"ignore_index": 3}
        self.outputs = {"Softmax": sm, "Loss": loss}
        self.check_output()
        self.check_grad(["Logits"], "Loss")

    def test_soft_label(self):
        self.setup()
        logits = np.random.rand(5, 7).astype(np.float64)
        label = np.random.rand(5, 7).astype(np.float64)
        label /= label.sum(-1, keepdims=True)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -(label * np.log(sm)).sum(-1, keepdims=True)
        self.inputs = {"Logits": logits, "Label": label}
        self.attrs = {"soft_label": True}
        self.outputs = {"Softmax": sm, "Loss": loss}
        self.check_output()


class TestLayerNorm(OpTest):
    op_type = "layer_norm"

    def test_basic(self):
        self.setup()
        x = np.random.rand(4, 10).astype(np.float64)
        scale = np.random.rand(10).astype(np.float64)
        bias = np.random.rand(10).astype(np.float64)
        m = x.mean(-1, keepdims=True)
        v = x.var(-1, keepdims=True)
        y = (x - m) / np.sqrt(v + 1e-5) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.attrs = {"epsilon": 1e-5, "begin_norm_axis": 1}
        self.outputs = {"Y": y, "Mean": m.ravel(), "Variance": v.ravel()}
        self.check_output(atol=1e-4)
        self.check_grad(["X", "Scale", "Bias"], "Y", max_relative_error=1e-2)


class TestBatchNorm(OpTest):
    op_type = "batch_norm"

    def test_train(self):
        self.setup()
        x = np.random.rand(4, 3, 5, 5).astype(np.float64)
        scale = np.random.rand(3).astype(np.float64)
        bias = np.random.rand(3).astype(np.float64)
        mean = np.zeros(3, np.float64)
        var = np.ones(3, np.float64)
        m = x.mean(axis=(0, 2, 3))
        v = x.var(axis=(0, 2, 3))
        y = (x - m.reshape(1, 3, 1, 1)) / np.sqrt(
            v.reshape(1, 3, 1, 1) + 1e-5) * scale.reshape(1, 3, 1, 1) + \
            bias.reshape(1, 3, 1, 1)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                       "Variance": var}
        self.attrs = {"epsilon": 1e-5, "momentum": 0.9}
        self.outputs = {"Y": y, "MeanOut": 0.9 * mean + 0.1 * m,
                        "VarianceOut": 0.9 * var + 0.1 * v}
        self.check_output(atol=1e-4)


class TestConv2d(OpTest):
    op_type = "conv2d"

    def test_basic(self):
        self.setup()
        x = np.random.rand(2, 3, 8, 8).astype(np.float64)
        w = np.random.rand(4, 3, 3, 3).astype(np.float64)
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {"strides": [1, 1], "paddings": [1, 1],
                      "dilations": [1, 1]}
        import jax
        ref = jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        self.outputs = {"Output": np.asarray(ref)}
        self.check_output()
        self.check_grad(["Input", "Filter"], "Output", delta=1e-4,
                        max_relative_error=2e-2)


class TestPool2d(OpTest):
    op_type = "pool2d"

    def test_max(self):
        self.setup()
        x = np.random.rand(2, 3, 4, 4).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "max", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0]}
        ref = x.reshape(2, 3, 2, 2, 2, 2).max(axis=(3, 5))
        self.outputs = {"Out": ref}
        self.check_output()

    def test_avg(self):
        self.setup()
        x = np.random.rand(2, 3, 4, 4).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "avg", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0]}
        ref = x.reshape(2, 3, 2, 2, 2, 2).mean(axis=(3, 5))
        self.outputs = {"Out": ref}
        self.check_output()

    def test_global(self):
        self.setup()
        x = np.random.rand(2, 3, 4, 4).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "avg", "global_pooling": True,
                      "ksize": [1, 1]}
        self.outputs = {"Out": x.mean(axis=(2, 3), keepdims=True)}
        self.check_output()


class TestLookupTable(OpTest):
    op_type = "lookup_table_v2"

    def test_basic(self):
        self.setup()
        w = np.random.rand(10, 4).astype(np.float64)
        ids = np.random.randint(0, 10, (3, 5)).astype(np.int64)
        self.inputs = {"W": w, "Ids": ids}
        self.outputs = {"Out": w[ids]}
        self.check_output()
        self.check_grad(["W"], "Out")


class TestManip(OpTest):
    def test_reshape(self):
        self.op_type = "reshape2"
        self.setup()
        x = np.random.rand(2, 6).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"shape": [3, 4]}
        self.outputs = {"Out": x.reshape(3, 4)}
        self.check_output(no_check_set=("XShape",))
        self.check_grad(["X"], "Out")

    def test_transpose(self):
        self.op_type = "transpose2"
        self.setup()
        x = np.random.rand(2, 3, 4).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"axis": [2, 0, 1]}
        self.outputs = {"Out": x.transpose(2, 0, 1)}
        self.check_output(no_check_set=("XShape",))

    def test_concat(self):
        self.op_type = "concat"
        self.setup()
        xs = [np.random.rand(2, 3).astype(np.float64) for _ in range(3)]
        self.inputs = {"X": xs}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": np.concatenate(xs, axis=1)}
        self.check_output()

    def test_split(self):
        self.op_type = "split"
        self.setup()
        x = np.random.rand(2, 6).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"axis": 1, "num": 3}
        self.outputs = {"Out": np.split(x, 3, axis=1)}
        self.check_output()

    def test_cast(self):
        self.op_type = "cast"
        self.setup()
        x = np.random.rand(3, 4).astype(np.float32)
        self.inputs = {"X": x}
        self.attrs = {"out_dtype": "float64"}
        self.outputs = {"Out": x.astype(np.float64)}
        self.check_output()

    def test_slice(self):
        self.op_type = "slice"
        self.setup()
        x = np.random.rand(4, 5, 6).astype(np.float64)
        self.inputs = {"Input": x}
        self.attrs = {"axes": [0, 2], "starts": [1, 2], "ends": [3, 5]}
        self.outputs = {"Out": x[1:3, :, 2:5]}
        self.check_output()
        self.check_grad(["Input"], "Out")

    def test_stack(self):
        self.op_type = "stack"
        self.setup()
        xs = [np.random.rand(2, 3).astype(np.float64) for _ in range(4)]
        self.inputs = {"X": xs}
        self.attrs = {"axis": 1}
        self.outputs = {"Y": np.stack(xs, axis=1)}
        self.check_output()

    def test_gather(self):
        self.op_type = "gather"
        self.setup()
        x = np.random.rand(10, 4).astype(np.float64)
        idx = np.array([1, 3, 5], np.int64)
        self.inputs = {"X": x, "Index": idx}
        self.outputs = {"Out": x[idx]}
        self.check_output()
        self.check_grad(["X"], "Out")

    def test_one_hot(self):
        self.op_type = "one_hot_v2"
        self.setup()
        x = np.array([1, 0, 3], np.int64)
        self.inputs = {"X": x}
        self.attrs = {"depth": 4}
        self.outputs = {"Out": np.eye(4, dtype=np.float32)[x]}
        self.check_output()

    def test_top_k(self):
        self.op_type = "top_k_v2"
        self.setup()
        x = np.array([[3.0, 1.0, 2.0], [0.5, 0.1, 0.9]], np.float64)
        self.inputs = {"X": x}
        self.attrs = {"k": 2}
        self.outputs = {"Out": np.array([[3.0, 2.0], [0.9, 0.5]]),
                        "Indices": np.array([[0, 2], [2, 0]])}
        self.check_output()


class TestDropout(OpTest):
    op_type = "dropout"

    def test_test_mode(self):
        self.setup()
        x = np.random.rand(4, 8).astype(np.float64)
        self.inputs = {"X": x}
        self.attrs = {"dropout_prob": 0.5, "is_test": True,
                      "dropout_implementation": "upscale_in_train"}
        self.outputs = {"Out": x}
        self.check_output(no_check_set=("Mask",))

    def test_train_statistics(self):
        self.setup()
        x = np.ones((100, 100), np.float32)
        self.inputs = {"X": x}
        self.attrs = {"dropout_prob": 0.3,
                      "dropout_implementation": "upscale_in_train",
                      "op_uid": 7}
        outs = self._run_forward()
        keep = np.asarray(outs["Mask"]).mean()
        assert abs(keep - 0.7) < 0.02
        # kept values upscaled
        o = np.asarray(outs["Out"])
        nz = o[o != 0]
        np.testing.assert_allclose(nz, 1.0 / 0.7, rtol=1e-5)

    def test_deterministic_replay(self):
        self.setup()
        x = np.random.rand(16, 16).astype(np.float32)
        self.inputs = {"X": x}
        self.attrs = {"dropout_prob": 0.5, "op_uid": 11,
                      "dropout_implementation": "upscale_in_train"}
        m1 = np.asarray(self._run_forward()["Mask"])
        m2 = np.asarray(self._run_forward()["Mask"])
        np.testing.assert_array_equal(m1, m2)


class TestOptimizerOps(OpTest):
    def test_sgd(self):
        self.op_type = "sgd"
        self.setup()
        p = np.random.rand(5, 3).astype(np.float32)
        g = np.random.rand(5, 3).astype(np.float32)
        lr = np.array([0.1], np.float32)
        self.inputs = {"Param": p, "Grad": g, "LearningRate": lr}
        self.outputs = {"ParamOut": p - 0.1 * g}
        self.check_output(atol=1e-6)

    def test_adam(self):
        self.op_type = "adam"
        self.setup()
        p = np.random.rand(4).astype(np.float32)
        g = np.random.rand(4).astype(np.float32)
        m1 = np.zeros(4, np.float32)
        m2 = np.zeros(4, np.float32)
        lr = np.array([0.001], np.float32)
        b1p = np.array([0.9], np.float32)
        b2p = np.array([0.999], np.float32)
        self.inputs = {"Param": p, "Grad": g, "LearningRate": lr,
                       "Moment1": m1, "Moment2": m2, "Beta1Pow": b1p,
                       "Beta2Pow": b2p}
        m1_o = 0.1 * g
        m2_o = 0.001 * g * g
        lr_t = 0.001 * np.sqrt(1 - b2p) / (1 - b1p)
        p_o = p - lr_t * m1_o / (np.sqrt(m2_o) + 1e-8)
        self.outputs = {"ParamOut": p_o, "Moment1Out": m1_o,
                        "Moment2Out": m2_o, "Beta1PowOut": b1p * 0.9,
                        "Beta2PowOut": b2p * 0.999}
        self.check_output(atol=1e-5)

    def test_momentum(self):
        self.op_type = "momentum"
        self.setup()
        p = np.random.rand(4).astype(np.float32)
        g = np.random.rand(4).astype(np.float32)
        v = np.random.rand(4).astype(np.float32)
        lr = np.array([0.01], np.float32)
        self.inputs = {"Param": p, "Grad": g, "Velocity": v,
                       "LearningRate": lr}
        self.attrs = {"mu": 0.9}
        v_o = 0.9 * v + g
        self.outputs = {"ParamOut": p - 0.01 * v_o, "VelocityOut": v_o}
        self.check_output(atol=1e-6)


class TestLosses(OpTest):
    def test_bce(self):
        self.op_type = "bce_loss"
        self.setup()
        x = np.random.uniform(0.1, 0.9, (4, 3)).astype(np.float64)
        l = np.random.randint(0, 2, (4, 3)).astype(np.float64)
        self.inputs = {"X": x, "Label": l}
        self.outputs = {"Out": -(l * np.log(x + 1e-12) +
                                 (1 - l) * np.log(1 - x + 1e-12))}
        self.check_output()
        self.check_grad(["X"], "Out")

    def test_huber(self):
        self.op_type = "huber_loss"
        self.setup()
        x = np.random.rand(5, 1).astype(np.float64)
        y = np.random.rand(5, 1).astype(np.float64)
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"delta": 0.5}
        r = y - x
        loss = np.where(np.abs(r) <= 0.5, 0.5 * r * r,
                        0.5 * (np.abs(r) - 0.25))
        self.outputs = {"Residual": r, "Out": loss}
        self.check_output()


class TestMetrics(OpTest):
    def test_accuracy(self):
        self.op_type = "accuracy"
        self.setup()
        idx = np.array([[0, 2], [1, 3], [2, 0]], np.int64)
        label = np.array([[2], [0], [1]], np.int64)
        self.inputs = {"Out": np.zeros((3, 2), np.float32), "Indices": idx,
                       "Label": label}
        self.outputs = {"Accuracy": np.array([1.0 / 3], np.float32),
                        "Correct": np.array([1], np.int32),
                        "Total": np.array([3], np.int32)}
        self.check_output()


class TestRandomOps(OpTest):
    def test_uniform_range(self):
        self.op_type = "uniform_random"
        self.setup()
        self.attrs = {"shape": [100, 100], "min": -2.0, "max": 3.0,
                      "op_uid": 3}
        out = np.asarray(self._run_forward()["Out"])
        assert out.min() >= -2.0 and out.max() < 3.0
        assert abs(out.mean() - 0.5) < 0.1

    def test_gaussian_moments(self):
        self.op_type = "gaussian_random"
        self.setup()
        self.attrs = {"shape": [200, 200], "mean": 1.0, "std": 2.0,
                      "op_uid": 5}
        out = np.asarray(self._run_forward()["Out"])
        assert abs(out.mean() - 1.0) < 0.05
        assert abs(out.std() - 2.0) < 0.05
