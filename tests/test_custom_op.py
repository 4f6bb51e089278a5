"""Custom op tests (reference: tests/python/unittest/test_operator.py
test_custom_op — sigmoid forward/backward through the Custom op)."""

import numpy as np

import mxnet_tpu as mx
import mxnet_tpu.operator as mxop


@mxop.register("test_sigmoid")
class SigmoidProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, in_shapes, in_dtypes=None):
        return Sigmoid()


class Sigmoid(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = 1.0 / (1.0 + np.exp(-x))
        self.assign(out_data[0], req[0], mx.nd.array(y))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        y = out_data[0].asnumpy()
        gy = out_grad[0].asnumpy()
        self.assign(in_grad[0], req[0], mx.nd.array(gy * y * (1.0 - y)))


def test_custom_imperative_forward():
    x = np.random.RandomState(0).randn(4, 5).astype(np.float32)
    out = mx.nd.Custom(mx.nd.array(x), op_type="test_sigmoid")
    np.testing.assert_allclose(out.asnumpy(), 1 / (1 + np.exp(-x)),
                               rtol=1e-5)


def test_custom_symbol_forward_backward():
    x = np.random.RandomState(1).randn(3, 4).astype(np.float32)
    data = mx.sym.Variable("data")
    sym = mx.sym.Custom(data, op_type="test_sigmoid", name="sig")
    # shape inference through the prop
    arg_shapes, out_shapes, _ = sym.infer_shape(data=(3, 4))
    assert out_shapes == [(3, 4)]
    exe = sym.simple_bind(mx.cpu(), grad_req="write", data=(3, 4))
    exe.arg_dict["data"][:] = x
    out = exe.forward(is_train=True)[0].asnumpy()
    y = 1 / (1 + np.exp(-x))
    np.testing.assert_allclose(out, y, rtol=1e-5)
    head = np.ones_like(x)
    exe.backward(out_grads=[mx.nd.array(head)])
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(),
                               y * (1 - y), rtol=1e-4)


def test_custom_in_module_training():
    """Custom op inside a trained graph: gradients flow through it."""
    # the default Uniform initializer draws from the GLOBAL numpy
    # stream; pin it so the outcome doesn't depend on suite order
    np.random.seed(2)
    mx.random.seed(2)
    rng = np.random.RandomState(2)
    X = rng.randn(80, 6).astype(np.float32)
    yv = (X.sum(axis=1) > 0).astype(np.float32)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Custom(net, op_type="test_sigmoid", name="act")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(X, yv, batch_size=20)
    mod = mx.mod.Module(net, context=mx.cpu())
    # 40 epochs, not 10: at 10 the accuracy over five seeds spans
    # 0.75-0.90 (this seed: exactly 0.85, a coin toss against the
    # threshold); at 40 every seed reaches 0.988
    mod.fit(it, num_epoch=40, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})
    acc = mod.score(mx.io.NDArrayIter(X, yv, batch_size=20), "acc")[0][1]
    assert acc > 0.85, acc


@mxop.register("test_scale2")
class Scale2Prop(mxop.CustomOpProp):
    """Two inputs, one output, an aux counter state."""

    def __init__(self, factor="2.0"):
        super().__init__(need_top_grad=True)
        self.factor = float(factor)

    def list_arguments(self):
        return ["a", "b"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return ["count"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], [[1]]

    def create_operator(self, ctx, in_shapes, in_dtypes=None):
        factor = self.factor

        class Scale2(mxop.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0],
                            (in_data[0] + in_data[1]) * factor)
                aux[0][:] = aux[0] + 1.0  # mutation round-trips

            def backward(self, req, out_grad, in_data, out_data,
                         in_grad, aux):
                self.assign(in_grad[0], req[0], out_grad[0] * factor)
                self.assign(in_grad[1], req[1], out_grad[0] * factor)

        return Scale2()


def test_custom_multi_input_attrs_and_aux():
    a = np.full((2, 3), 1.0, np.float32)
    b = np.full((2, 3), 2.0, np.float32)
    sym = mx.sym.Custom(mx.sym.Variable("a"), mx.sym.Variable("b"),
                        op_type="test_scale2", factor="3.0", name="s2")
    exe = sym.simple_bind(mx.cpu(), grad_req="write", a=(2, 3), b=(2, 3))
    exe.arg_dict["a"][:] = a
    exe.arg_dict["b"][:] = b
    out = exe.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(out, (a + b) * 3.0)
    # aux mutated by the host op is visible after the run
    assert float(exe.aux_dict["s2_count"].asnumpy()[0]) >= 1.0
    exe.forward(is_train=True)
    exe.backward(out_grads=[mx.nd.ones((2, 3))])
    np.testing.assert_allclose(exe.grad_dict["a"].asnumpy(),
                               np.full((2, 3), 3.0))


def test_custom_imperative_accepts_name():
    x = np.ones((2, 2), np.float32)
    out = mx.nd.Custom(mx.nd.array(x), op_type="test_sigmoid", name="act")
    np.testing.assert_allclose(out.asnumpy(), 1 / (1 + np.exp(-x)))


@mxop.register("test_aux_bwd")
class AuxBwdProp(mxop.CustomOpProp):
    """Backward reads aux state that forward wrote."""

    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_auxiliary_states(self):
        return ["state"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], [[1]]

    def create_operator(self, ctx, in_shapes, in_dtypes=None):
        class AuxBwd(mxop.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0], in_data[0])
                aux[0][:] = mx.nd.array(np.array([7.0], np.float32))

            def backward(self, req, out_grad, in_data, out_data,
                         in_grad, aux):
                scale = float(aux[0].asnumpy()[0])
                self.assign(in_grad[0], req[0], out_grad[0] * scale)

        return AuxBwd()


def test_custom_backward_sees_forward_aux():
    sym = mx.sym.Custom(mx.sym.Variable("data"), op_type="test_aux_bwd",
                        name="ab")
    exe = sym.simple_bind(mx.cpu(), grad_req="write", data=(2, 2))
    exe.arg_dict["data"][:] = np.ones((2, 2), np.float32)
    exe.forward(is_train=True)
    exe.backward(out_grads=[mx.nd.ones((2, 2))])
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(),
                               np.full((2, 2), 7.0))


@mxop.register("test_custom_loss")
class CustomLossProp(mxop.CustomOpProp):
    """need_top_grad=False: the op is a loss head producing its own grad."""

    def __init__(self):
        super().__init__(need_top_grad=False)

    def create_operator(self, ctx, in_shapes, in_dtypes=None):
        class L(mxop.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0], in_data[0])

            def backward(self, req, out_grad, in_data, out_data,
                         in_grad, aux):
                # d/dx of 0.5*x^2 — ignores out_grad like reference
                # loss-style custom ops
                self.assign(in_grad[0], req[0], in_data[0])

        return L()


def test_custom_loss_head_backward_without_out_grads():
    """The reference custom-loss workflow: backward() with no out_grads."""
    sym = mx.sym.Custom(mx.sym.Variable("data"),
                        op_type="test_custom_loss", name="loss")
    exe = sym.simple_bind(mx.cpu(), grad_req="write", data=(2, 3))
    x = np.random.RandomState(0).randn(2, 3).astype(np.float32)
    exe.arg_dict["data"][:] = x
    exe.forward(is_train=True)
    exe.backward()  # no out_grads: op is recognized as a loss head
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(), x,
                               rtol=1e-5)
