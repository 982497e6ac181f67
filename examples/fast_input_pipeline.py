"""TPU-first input pipeline: raw uint8 over the wire, normalize on device.

The reference feeds fit() float arrays that a DataNormalization already
transformed on the host (ImagePreProcessingScaler via DataVec) — so every
batch crosses host->HBM as float32. On TPU the affine scale fuses into the
first convolution for free, so the wire can carry the raw uint8 pixels
(4x fewer bytes) and bf16 labels (2x fewer) while AsyncDataSetIterator's
prefetch thread applies the normalizer ON DEVICE, overlapped with the
training step. What the fed path delivers on the chip is not measured yet
(ROADMAP S4).

reference: datasets/iterator/AsyncDataSetIterator.java:75-76 (device-pinned
prefetch), ImagePreProcessingScaler.java (host-side transform replaced by
Normalizer.device_apply here).
"""
import _common  # noqa: F401

import numpy as np

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.datasets.iterators import (ArraysDataSetIterator,
                                                   AsyncDataSetIterator)
from deeplearning4j_tpu.datasets.normalizers import ImagePreProcessingScaler
from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer, DenseLayer,
                                               OutputLayer, SubsamplingLayer)

rng = np.random.default_rng(0)

# raw uint8 images, as an ImageRecordReader would yield them
x8 = rng.integers(0, 256, (256, 28, 28, 1), dtype=np.uint8)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 256)]

def build_net():
    # fresh configuration per network: conf carries iteration/epoch
    # counters, so sharing one instance would skew LR schedules between
    # the two arms
    conf = (NeuralNetConfiguration.Builder()
            .seed(123)
            .updater("adam").learning_rate(1e-3)
            .data_type("bfloat16")
            .list()
            .layer(0, ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                       activation="relu"))
            .layer(1, SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(2, DenseLayer(n_out=64, activation="relu"))
            .layer(3, OutputLayer(n_out=10, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.convolutional(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf).init()


net = build_net()

scaler = ImagePreProcessingScaler()          # [0, 255] -> [0, 1]
base = ArraysDataSetIterator((x8, y), batch_size=64)
it = AsyncDataSetIterator(
    base,
    queue_size=4,
    transfer_dtype="bfloat16",     # float arrays (labels) ship as bf16
    # uint8 pixels scale on device; pass the model dtype so the staged
    # batch is written once in bf16 (safe: the step casts to bf16 anyway)
    device_transform=scaler.as_device_transform("bfloat16"),
)
net.fit(it, num_epochs=3)
score = float(net._score)
print("final score:", score)

# same data through the reference-style host-side f32 path — identical
# model (fixed seed => identical init)
xf = x8.astype(np.float32) / 255.0
net2 = build_net()
itf = ArraysDataSetIterator((xf, y), batch_size=64)
net2.fit(AsyncDataSetIterator(itf, queue_size=4), num_epochs=3)
print("host-f32 score:", float(net2._score))

print(np.isfinite(score) and score > 0)
