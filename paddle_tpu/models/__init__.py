"""Model zoo (reference capability: PaddleNLP/PaddleMIX model recipes
trained through the framework — SURVEY.md §7 phase 8)."""
from . import dit  # noqa: F401
from . import falcon_h1  # noqa: F401
from . import llama  # noqa: F401
from . import moe  # noqa: F401
from . import ocr  # noqa: F401
from . import phi4flash  # noqa: F401
from . import zaya  # noqa: F401

__all__ = ["llama", "moe", "dit", "ocr", "falcon_h1", "phi4flash",
           "zaya"]
