"""Model zoo: the reference's benchmark + book-test model families, built on
the paddle_tpu layers API (reference configs: benchmark/paddle/image/{alexnet,
googlenet,resnet,vgg,smallnet_mnist_cifar}.py, benchmark/paddle/rnn/rnn.py,
python/paddle/v2/fluid/tests/book/*).

Each builder appends ops to the current default program and returns the
output variable(s); pair with ``paddle_tpu.optimizer`` and ``Executor`` for
training, or use the packaged ``build_*_trainer`` convenience wrappers.
"""
from .mnist import mlp as mnist_mlp, lenet as mnist_lenet
from .alexnet import alexnet
from .vgg import vgg16, vgg19, vgg_cifar
from .resnet import resnet_imagenet, resnet50, resnet_cifar
from .googlenet import googlenet
from .lstm_textcls import lstm_text_classification
from .seq2seq import seq2seq_attention, seq2seq_infer
from .wide_deep import wide_deep
from .olmoe import olmoe
from .ouro import ouro, ouro_loss
from .lfm2 import lfm2, lfm2_loss
from .granite_hybrid import granite_hybrid, granite_hybrid_loss
from .nemotron_h import nemotron_h, nemotron_h_loss

__all__ = [
    "mnist_mlp", "mnist_lenet", "alexnet", "vgg16", "vgg19", "vgg_cifar",
    "resnet_imagenet", "resnet50", "resnet_cifar", "googlenet",
    "lstm_text_classification", "seq2seq_attention", "seq2seq_infer", "wide_deep",
    "olmoe", "ouro", "ouro_loss", "lfm2", "lfm2_loss",
    "granite_hybrid", "granite_hybrid_loss", "nemotron_h", "nemotron_h_loss",
]
