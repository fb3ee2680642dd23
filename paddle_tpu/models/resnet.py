"""ResNet family.

Reference parity: incubate/hapi/vision/models/resnet.py (+ the
dist_se_resnext.py test fixture); BASELINE.md's ResNet-50 images/sec/chip
metric runs on this model.

TPU note: ``data_format`` selects the activation layout end-to-end.
"NCHW" is the paddle-default API surface; "NHWC" keeps activations in the
channels-last layout the TPU vector units natively tile (lane dim = C),
which removes the relayout copies XLA otherwise inserts around every conv
— the same reason the reference's cudnn path prefers NHWC tensor cores
(/root/reference/paddle/fluid/operators/conv_cudnn_op.cu.cc exhaustive-
search layouts). Weights stay OIHW in both modes.
"""
from __future__ import annotations

from ..nn import functional as F
from ..nn.layer_base import Layer
from ..nn.layers import (
    AdaptiveAvgPool2D,
    BatchNorm2D,
    Conv2D,
    Linear,
    MaxPool2D,
    Sequential,
    fused_conv_bn_relu,
)


class BasicBlock(Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None, data_format="NCHW"):
        super().__init__()
        df = dict(data_format=data_format)
        self.conv1 = Conv2D(inplanes, planes, 3, stride=stride, padding=1, bias_attr=False, **df)
        self.bn1 = BatchNorm2D(planes, **df)
        self.conv2 = Conv2D(planes, planes, 3, padding=1, bias_attr=False, **df)
        self.bn2 = BatchNorm2D(planes, **df)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        # conv->bn->relu triples go through fused_conv_bn_relu
        # (FLAGS_use_fused_conv_bn), whose pallas path takes pointwise
        # convs only: this 3x3 runs XLA's convolution either way. bn2
        # feeds the residual add, not a relu, so it stays unfused
        out = fused_conv_bn_relu(self.conv1, self.bn1, x)
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return F.relu(out + identity)


class BottleneckBlock(Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None, data_format="NCHW"):
        super().__init__()
        df = dict(data_format=data_format)
        self.conv1 = Conv2D(inplanes, planes, 1, bias_attr=False, **df)
        self.bn1 = BatchNorm2D(planes, **df)
        self.conv2 = Conv2D(planes, planes, 3, stride=stride, padding=1, bias_attr=False, **df)
        self.bn2 = BatchNorm2D(planes, **df)
        self.conv3 = Conv2D(planes, planes * self.expansion, 1, bias_attr=False, **df)
        self.bn3 = BatchNorm2D(planes * self.expansion, **df)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        # 2 of the 3 convs per bottleneck carry a bn+relu epilogue: the
        # pointwise conv1 takes the pallas kernels, the 3x3 conv2 XLA's
        # convolution (ops/pallas/conv_bn_relu.py); bn3 feeds the
        # residual add and stays unfused
        out = fused_conv_bn_relu(self.conv1, self.bn1, x)
        out = fused_conv_bn_relu(self.conv2, self.bn2, out)
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return F.relu(out + identity)


class ResNet(Layer):
    def __init__(self, block, depth_cfg, num_classes=1000, with_pool=True,
                 data_format="NCHW"):
        super().__init__()
        self.inplanes = 64
        self.data_format = data_format
        df = dict(data_format=data_format)
        self.conv1 = Conv2D(3, 64, 7, stride=2, padding=3, bias_attr=False, **df)
        self.bn1 = BatchNorm2D(64, **df)
        self.maxpool = MaxPool2D(kernel_size=3, stride=2, padding=1, **df)
        self.layer1 = self._make_layer(block, 64, depth_cfg[0])
        self.layer2 = self._make_layer(block, 128, depth_cfg[1], stride=2)
        self.layer3 = self._make_layer(block, 256, depth_cfg[2], stride=2)
        self.layer4 = self._make_layer(block, 512, depth_cfg[3], stride=2)
        self.with_pool = with_pool
        if with_pool:
            self.avgpool = AdaptiveAvgPool2D((1, 1), **df)
        self.num_classes = num_classes
        if num_classes > 0:
            self.fc = Linear(512 * block.expansion, num_classes)

    def _make_layer(self, block, planes, blocks, stride=1):
        df = dict(data_format=self.data_format)
        downsample = None
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = Sequential(
                Conv2D(self.inplanes, planes * block.expansion, 1,
                       stride=stride, bias_attr=False, **df),
                BatchNorm2D(planes * block.expansion, **df),
            )
        layers = [block(self.inplanes, planes, stride, downsample, **df)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes, **df))
        return Sequential(*layers)

    def forward(self, x):
        x = self.maxpool(fused_conv_bn_relu(self.conv1, self.bn1, x))
        x = self.layer4(self.layer3(self.layer2(self.layer1(x))))
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            from .. import ops

            x = ops.flatten(x, 1)
            x = self.fc(x)
        return x


def resnet18(**kw):
    return ResNet(BasicBlock, [2, 2, 2, 2], **kw)


def resnet34(**kw):
    return ResNet(BasicBlock, [3, 4, 6, 3], **kw)


def resnet50(**kw):
    return ResNet(BottleneckBlock, [3, 4, 6, 3], **kw)


def resnet101(**kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], **kw)


def resnet152(**kw):
    return ResNet(BottleneckBlock, [3, 8, 36, 3], **kw)
