"""Architecture configs: 10 assigned archs + the paper's simulator models."""

from .base import (  # noqa: F401
    ARCH_IDS,
    ArchConfig,
    AttnConfig,
    LinearAttnConfig,
    MLAConfig,
    MoEConfig,
    SHAPES,
    SSMConfig,
    ShapeSpec,
    YarnConfig,
    all_archs,
    cell_is_skipped,
    get_arch,
)
