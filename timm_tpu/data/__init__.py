from .auto_augment import (
    AugMixAugment, AutoAugment, RandAugment, augment_and_mix_transform,
    auto_augment_transform, rand_augment_transform,
)
from .config import resolve_data_config, resolve_model_data_config
from .constants import (
    DEFAULT_CROP_MODE, DEFAULT_CROP_PCT, IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD,
    IMAGENET_INCEPTION_MEAN, IMAGENET_INCEPTION_STD, OPENAI_CLIP_MEAN, OPENAI_CLIP_STD,
)
from .dataset import AugMixDataset, ImageDataset, TokenWindows
from .dataset_factory import create_dataset
from .device_augment import (
    DeviceAugment, DeviceAugmentStage, NaFlexDeviceAugment,
    augment_image_batch, augment_image_batch_np, augment_naflex_batch,
)
from .loader import StreamingLoader, ThreadedLoader, create_loader
from .readers_streaming import ReaderImageInTar, ReaderTfds, ReaderWds, assign_shards
from .mixup import FastCollateMixup, Mixup
from .naflex_loader import NaFlexCollator, NaFlexLoader, calculate_naflex_batch_size, create_naflex_loader
from .random_erasing import RandomErasing
from .readers import ReaderImageFolder, create_reader
from .real_labels import RealLabelsImagenet
from .transforms import (
    CenterCrop, CenterCropOrPad, Compose, RandomResizedCropAndInterpolation,
    Resize, ResizeKeepRatio, ToNumpy,
)
from .transforms_factory import create_transform
