"""Model zoo.

Reference parity: the models used by the reference's tests and hapi
(python/paddle/incubate/hapi/vision/models/, tests/book/, the dist-test
fixtures dist_mnist.py / dist_se_resnext.py / dist_transformer.py).
Flagship = BERT (the BASELINE.md headline metric is BERT-base
tokens/sec/chip).
"""
from .lenet import LeNet  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertModel,
    BertForPretraining,
    BertPretrainingCriterion,
    bert_base_config,
    bert_tiny_config,
    bert_sharding_rules,
    bert_pipeline_stages,
    ernie_base_config,
    ErnieModel,
    ErnieForPretraining,
    knowledge_masking,
    BertEmbeddingStage,
    BertEncoderStage,
    BertHeadStage,
)
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152  # noqa: F401
from .word2vec import Word2Vec  # noqa: F401
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19  # noqa: F401
from .mobilenet import (  # noqa: F401
    MobileNetV1,
    MobileNetV2,
    mobilenet_v1,
    mobilenet_v2,
)
from .seq2seq import TransformerSeq2Seq  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    gpt_tiny_config,
    load_gpt_model,
    save_gpt_model,
    truncated_draft,
)
from .solar_open2 import (  # noqa: F401
    HybridMoEConfig,
    HybridMoEForCausalLM,
)
from .exaone_moe import (  # noqa: F401
    ExaoneMoEConfig,
    ExaoneMoEForCausalLM,
)
from .longcat_flash import (  # noqa: F401
    LongcatFlashConfig,
    LongcatFlashForCausalLM,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHForCausalLM,
)
from .minicpm_sala import (  # noqa: F401
    MiniCPMSALAConfig,
    MiniCPMSALAForCausalLM,
)
from .se_resnext import (  # noqa: F401
    SEResNeXt,
    se_resnext50_32x4d,
    se_resnext101_32x4d,
)
