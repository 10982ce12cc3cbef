"""Bundled model zoo (SURVEY.md §2 "Example models")."""

from .cnn import JaxCnn
from .densenet import JaxDenseNet
from .enas import JaxEnas
from .feedforward import JaxFeedForward
from .lm import JaxTransformerLM
from .lm_lfm2 import JaxLfm2MoeLM
from .lm_moe import JaxLatentMoELM
from .pos_tagger import JaxPosTagger
from .sk import SkDt, SkSvm
from .tabular import JaxTabMlpClf, JaxTabMlpReg
from .transformer import JaxTransformerTagger
from .vit import JaxViT

__all__ = ["JaxFeedForward", "JaxCnn", "JaxDenseNet", "JaxEnas", "JaxViT",
           "JaxPosTagger", "SkDt", "SkSvm", "JaxTabMlpClf",
           "JaxTabMlpReg", "JaxTransformerTagger", "JaxTransformerLM",
           "JaxLatentMoELM", "JaxLfm2MoeLM"]
