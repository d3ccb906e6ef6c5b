from repro.configs.base import (  # noqa: F401
    ALIASES,
    ARCH_IDS,
    ArchConfig,
    ShapeConfig,
    all_archs,
    get_arch,
    get_reduced,
)
