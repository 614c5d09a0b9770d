"""paddle_tpu.models — model zoo for the BASELINE configs (reference:
python/paddle/vision/models + test/auto_parallel/get_gpt_model.py)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTForCausalLM, GPTForCausalLMPipe, GPTModel,
    GPTPretrainingCriterion, gpt_1p3b, gpt_13b, gpt_small, gpt_tiny,
)
from .seq2seq import Seq2SeqTransformer  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    bert_base, bert_tiny,
)
from .lenet import LeNet  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50  # noqa: F401
from .vision_zoo import (  # noqa: F401
    AlexNet, MobileNetV1, MobileNetV2, VGG, alexnet, vgg11, vgg13, vgg16,
    vgg19,
)
from .mla_moe import (  # noqa: F401
    MLAMoEConfig, MLAMoEForCausalLM, mla_moe_tiny,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig, AfmoeForCausalLM, afmoe_tiny,
)
from .kda_mla_moe import (  # noqa: F401
    KDAMLAMoEConfig, KDAMLAMoEForCausalLM, kda_mla_moe_tiny,
)
