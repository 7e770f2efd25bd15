from .block_diffusion_lm import BlockDiffusionLMTask
from .causal_lm import CausalLMTask
from .classification import ClassificationTask, NaFlexClassificationTask
from .distillation import FeatureDistillationTask, LogitDistillationTask
from .token_distillation import TokenDistillationTask
from .task import TrainingTask
