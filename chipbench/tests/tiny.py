"""Builder of the tests' tiny ResNet v1 (the zoo's class at toy sizes)."""


def build(**kw):
    from mxnet_tpu.gluon.model_zoo.vision import resnet
    block = {"bottleneck": resnet.BottleneckV1, "basic": resnet.BasicBlockV1}[kw.pop("block")]
    return resnet.ResNetV1(block, **kw)
