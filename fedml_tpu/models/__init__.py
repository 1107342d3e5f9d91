"""Flax model zoo — TPU-native rebuild of reference fedml_api/model/ (§2.6).

`create_model(model_name, output_dim, **kw)` mirrors the reference's factory
(fedml_experiments/distributed/fedavg/main_fedavg.py:359-394).
"""
from __future__ import annotations

from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.models.cnn import CNNOriginalFedAvg, CNNDropOut
from fedml_tpu.models.rnn import RNNOriginalFedAvg, RNNStackOverflow
from fedml_tpu.models.resnet_gn import ResNet18GN
from fedml_tpu.models.resnet_cifar import resnet20, resnet32, resnet44, resnet56
from fedml_tpu.models.mobilenet import MobileNetV1
from fedml_tpu.models.mobilenet_v3 import MobileNetV3
from fedml_tpu.models.efficientnet import EfficientNet
from fedml_tpu.models.vgg import VGG11, VGG16


def create_model(model_name: str, output_dim: int, input_dim: int | None = None,
                 **kw):
    """Model factory keyed by the reference's --model names."""
    name = model_name.lower()
    if name == "lr":
        return LogisticRegression(num_classes=output_dim, flatten=True)
    if name == "cnn":
        return CNNOriginalFedAvg(num_classes=output_dim, **kw)
    if name == "cnn_dropout":
        return CNNDropOut(num_classes=output_dim, **kw)
    if name == "rnn":
        return RNNOriginalFedAvg(vocab_size=kw.pop("vocab_size", 90), **kw)
    if name == "rnn_stackoverflow":
        # vocab follows output_dim (callers pass the dataset's class
        # count, 10,004 for real stackoverflow) — ignoring it built a
        # 10,004-way softmax under reduced-vocab smokes
        return RNNStackOverflow(vocab_size=kw.pop("vocab_size",
                                                  output_dim), **kw)
    if name == "transformer":
        # beyond-reference: causal decoder LM for the next-token tasks
        # (models/transformer.py) — vocab from the dataset's class count
        from fedml_tpu.models.transformer import TransformerLM
        return TransformerLM(vocab_size=output_dim, **kw)
    if name == "looped_lm":
        # a decoder LM whose layer stack runs several times (Ouro's
        # family, models/looped_lm.py); every width is a keyword
        from fedml_tpu.models.looped_lm import LoopedDecoderLM
        return LoopedDecoderLM(vocab_size=output_dim, **kw)
    if name == "lfm2_moe":
        # gated short convolutions + grouped-query attention + sparse
        # experts, adapters over a frozen base (LFM2-MoE's family,
        # models/lfm2_moe.py); every width is a keyword, and a pattern
        # that arrives as a JSON list becomes the tuple a Module hashes
        from fedml_tpu.models.lfm2_moe import Lfm2MoeLM
        kw = {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}
        return Lfm2MoeLM(vocab_size=output_dim, **kw)
    if name == "deepseek_v2":
        # multi-head latent attention + group-limited sparse experts beside
        # shared ones, adapters over a frozen base (DeepSeek-V2's family,
        # models/deepseek_v2.py); every width is a keyword
        from fedml_tpu.models.deepseek_v2 import DeepSeekV2LM
        kw = {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}
        return DeepSeekV2LM(vocab_size=output_dim, **kw)
    if name == "cohere2_moe":
        # parallel blocks of sliding-window / full attention and sigmoid-routed
        # experts beside averaged shared ones, adapters over a frozen base
        # (Command A+'s family, models/cohere2_moe.py); every width is a keyword
        from fedml_tpu.models.cohere2_moe import Cohere2MoeLM
        kw = {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}
        return Cohere2MoeLM(vocab_size=output_dim, **kw)
    if name == "xing4":
        # four residual streams mixed by manifold-constrained hyper-connections
        # around latent attention and bias-selected sigmoid-routed experts,
        # adapters over a frozen base (Xing4.0's family, models/xing4.py);
        # every width is a keyword
        from fedml_tpu.models.xing4 import Xing4LM
        kw = {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}
        return Xing4LM(vocab_size=output_dim, **kw)
    if name in ("resnet18_gn", "resnet18"):
        return ResNet18GN(num_classes=output_dim, **kw)
    if name == "resnet56":
        return resnet56(num_classes=output_dim, **kw)
    if name == "resnet20":
        return resnet20(num_classes=output_dim, **kw)
    if name == "mobilenet":
        return MobileNetV1(num_classes=output_dim, **kw)
    if name == "mobilenet_v3":
        return MobileNetV3(num_classes=output_dim, **kw)
    if name.startswith("efficientnet"):     # efficientnet-b0 .. -b7
        variant = name.rsplit("-", 1)[-1] if "-" in name else "b0"
        return EfficientNet(num_classes=output_dim, variant=variant, **kw)
    if name == "darts":
        from fedml_tpu.models.darts import DARTS_V2, DartsNetwork
        return DartsNetwork(num_classes=output_dim,
                            genotype=kw.pop("genotype", DARTS_V2), **kw)
    if name in ("vgg11",):
        return VGG11(num_classes=output_dim, **kw)
    if name in ("vgg16",):
        return VGG16(num_classes=output_dim, **kw)
    if name == "segnet":
        from fedml_tpu.models.segnet import SegEncoderDecoder
        return SegEncoderDecoder(num_classes=output_dim, **kw)
    raise ValueError(f"unknown model {model_name!r}")
