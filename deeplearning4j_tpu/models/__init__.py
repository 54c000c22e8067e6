"""Model zoo (L7) — parity with deeplearning4j-zoo's 13 models (SURVEY.md §2.8)
plus the transformer family the TPU build adds."""

from .cnn import (VGG16, VGG19, YOLO2, AlexNet, Darknet19, FaceNetNN4Small2,
                  GoogLeNet, InceptionResNetV1, LeNet, ResNet50, SimpleCNN,
                  TinyYOLO)
from .rnn import GravesLSTMCharRNN, TextGenerationLSTM
from .transformer import (BertBase, CausalLM, Glm4MoeLiteLM, LagunaLM,
                          MiniCpmSalaLM, OlmoeLM, sharded_lm_step)
from .zoo import ZOO_REGISTRY, ZooModel, model_by_name, register_model

__all__ = ["AlexNet", "BertBase", "CausalLM", "Darknet19", "FaceNetNN4Small2",
           "Glm4MoeLiteLM", "GoogLeNet", "GravesLSTMCharRNN", "InceptionResNetV1", "LagunaLM", "LeNet",
           "MiniCpmSalaLM", "OlmoeLM",
           "ResNet50", "SimpleCNN", "TextGenerationLSTM", "TinyYOLO", "VGG16",
           "VGG19", "YOLO2", "ZOO_REGISTRY", "ZooModel", "model_by_name",
           "register_model", "sharded_lm_step"]
