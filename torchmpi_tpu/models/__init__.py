from .deltanet import GatedDeltaDecoder, GatedDeltaDecoderBlock
from .decoder import (
    MoEDecoder,
    MoEDecoderBlock,
    Rotary,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from .hybrid import HybridDecoder, HybridDecoderBlock, Multipliers
from .lm import init_lm_params, make_lm_loss_fn
from .lm_head import VocabHead
from .mlp import MLP6
from .mnist import (
    LeNet,
    LogisticRegression,
    accuracy,
    cross_entropy_loss,
    init_params,
    make_loss_fn,
)
from .resnet import (
    ResNet,
    ResNet18,
    ResNet50,
    init_resnet,
    make_stateful_loss_fn,
)
from .retentive import RetentionDecoder, RetentionDecoderBlock
from .transformer import LongContextTransformer, RingAttentionBlock

__all__ = [
    "LogisticRegression",
    "LeNet",
    "MLP6",
    "ResNet",
    "ResNet18",
    "ResNet50",
    "LongContextTransformer",
    "RingAttentionBlock",
    "MoEDecoder",
    "MoEDecoderBlock",
    "Rotary",
    "HybridDecoder",
    "HybridDecoderBlock",
    "Multipliers",
    "RetentionDecoder",
    "RetentionDecoderBlock",
    "GatedDeltaDecoder",
    "GatedDeltaDecoderBlock",
    "VocabHead",
    "make_moe_lm_loss_fn",
    "init_moe_state",
    "cross_entropy_loss",
    "accuracy",
    "make_loss_fn",
    "make_stateful_loss_fn",
    "init_resnet",
    "init_params",
    "init_lm_params",
    "make_lm_loss_fn",
]
