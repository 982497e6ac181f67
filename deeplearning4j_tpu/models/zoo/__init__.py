from .char_rnn import char_rnn, char_rnn_conf
from .classic_cnns import (alexnet, alexnet_conf, googlenet,
                           googlenet_conf, vgg16, vgg16_conf)
from .joyai import joyai_conf
from .keye_vl import keye_vl_conf
from .laguna import laguna_conf
from .nemotron_h import nemotron_h_conf
from .lenet import lenet, lenet_conf
from .resnet import resnet50, resnet50_conf

__all__ = ["alexnet", "alexnet_conf", "char_rnn", "char_rnn_conf",
           "googlenet", "googlenet_conf", "joyai_conf", "keye_vl_conf", "laguna_conf",
           "lenet", "lenet_conf", "nemotron_h_conf", "resnet50", "resnet50_conf", "vgg16", "vgg16_conf"]
