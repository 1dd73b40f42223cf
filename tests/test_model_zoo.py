"""Model zoo smoke tests (reference model: test_gluon_model_zoo.py).

Each model builds, hybridizes, and runs forward on a small batch.
Input sizes are the reference's canonical ones, shrunk where the
architecture allows to keep CPU CI fast.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import vision


def _smoke(name, input_size=224, classes=10, batch=1):
    net = vision.get_model(name, classes=classes)
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    x = mx.nd.random.normal(shape=(batch, 3, input_size, input_size))
    out = net(x)
    assert out.shape == (batch, classes)
    assert np.isfinite(out.asnumpy()).all()
    return net


# zoo construction stays tier-1 via the save-load roundtrip; the train
# path through a zoo resnet runs every tier-1 round in
# test_chip_smoke.py::test_train_resnet_phase_holds_the_fused_plan
@pytest.mark.slow
def test_resnet18_v1_forward_backward():
    net = vision.get_model("resnet18_v1", classes=10)
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    x = mx.nd.random.normal(shape=(2, 3, 64, 64))
    y = mx.nd.array([1.0, 3.0])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    grads = [p.grad() for p in net.collect_params().values()
             if p.grad_req != "null"]
    assert all(np.isfinite(g.asnumpy()).all() for g in grads)


@pytest.mark.slow
def test_resnet34_v2():
    _smoke("resnet34_v2", input_size=64)


# zoo construction stays tier-1 via the save-load roundtrip and
# test_chip_smoke.py (trains a zoo resnet every tier-1 round)
@pytest.mark.slow
def test_resnet50_v1_shape():
    net = vision.get_model("resnet50_v1", classes=7)
    net.initialize()
    out = net(mx.nd.random.normal(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 7)


@pytest.mark.slow
def test_alexnet():
    _smoke("alexnet", input_size=224)


@pytest.mark.slow
def test_vgg11():
    _smoke("vgg11", input_size=224)


@pytest.mark.slow
def test_vgg11_bn():
    _smoke("vgg11_bn", input_size=224)


@pytest.mark.slow
def test_squeezenet():
    _smoke("squeezenet1.1", input_size=224)


@pytest.mark.slow
def test_densenet121():
    _smoke("densenet121", input_size=64)


@pytest.mark.slow
def test_mobilenet():
    _smoke("mobilenet0.25", input_size=64)


@pytest.mark.slow
def test_mobilenet_v2():
    _smoke("mobilenetv2_0.25", input_size=64)


@pytest.mark.slow
def test_inception_v3():
    _smoke("inceptionv3", input_size=299)


def test_get_model_unknown():
    with pytest.raises(mx.MXNetError):
        vision.get_model("not_a_model")


def test_model_save_load_roundtrip(tmp_path):
    net = vision.get_model("resnet18_v1", classes=4)
    net.initialize()
    x = mx.nd.random.normal(shape=(1, 3, 32, 32))
    ref = net(x).asnumpy()
    f = str(tmp_path / "r18.params")
    net.save_parameters(f)
    net2 = vision.get_model("resnet18_v1", classes=4)
    net2.load_parameters(f)
    out = net2(x).asnumpy()
    np.testing.assert_allclose(ref, out, rtol=1e-5, atol=1e-6)
