from .base import (ARCH_IDS, CNN_IDS, SHAPES, SMOKE_SHAPES, LatentMoEConfig,
                   ModelConfig, ShapeCell, applicable_shapes, canonical,
                   get_config)
