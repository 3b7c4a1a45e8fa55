from ray_lightning_tpu.models.boring import (
    BoringModel,
    LightningMNISTClassifier,
    RandomDataset,
)
from ray_lightning_tpu.models.command import (
    Command,
    CommandConfig,
    CommandLightningModule,
)
from ray_lightning_tpu.models.evabyte import (
    EvaByte,
    EvaByteConfig,
    EvaByteLightningModule,
)
from ray_lightning_tpu.models.gpt import GPT, GPTConfig, GPTLightningModule
from ray_lightning_tpu.models.kimi_linear import (
    KimiLinear,
    KimiLinearConfig,
    KimiLinearLightningModule,
)
from ray_lightning_tpu.models.xing import (
    Xing,
    XingConfig,
    XingLightningModule,
)
from ray_lightning_tpu.models.zaya import (
    Zaya,
    ZayaConfig,
    ZayaLightningModule,
)
from ray_lightning_tpu.models.pipeline_gpt import PipelinedGPT
from ray_lightning_tpu.models.resnet import (
    ResNet,
    ResNetConfig,
    ResNetLightningModule,
)
from ray_lightning_tpu.models.bert import (
    BertClassifier,
    BertConfig,
    BertEncoder,
    BertForMaskedLM,
    BertLightningModule,
    BertMLMModule,
)

__all__ = [
    "BoringModel",
    "LightningMNISTClassifier",
    "RandomDataset",
    "Command",
    "CommandConfig",
    "CommandLightningModule",
    "EvaByte",
    "EvaByteConfig",
    "EvaByteLightningModule",
    "GPT",
    "GPTConfig",
    "GPTLightningModule",
    "PipelinedGPT",
    "ResNet",
    "ResNetConfig",
    "ResNetLightningModule",
    "BertClassifier",
    "BertConfig",
    "BertEncoder",
    "BertLightningModule",
    "BertForMaskedLM",
    "BertMLMModule",
    "Xing",
    "XingConfig",
    "XingLightningModule",
    "Zaya",
    "ZayaConfig",
    "ZayaLightningModule",
    "KimiLinear",
    "KimiLinearConfig",
    "KimiLinearLightningModule",
]
