"""Public model API (parity with ref `src/jimm/models/__init__.py:1-9`)."""

from jimm_tpu.models.clip import CLIP
from jimm_tpu.models.ouro import Ouro
from jimm_tpu.models.siglip import SigLIP
from jimm_tpu.models.vit import VisionTransformer

__all__ = ["VisionTransformer", "CLIP", "SigLIP", "Ouro"]
