"""Model zoo (flax/jax model builders for the jax filter backend)."""
from . import zoo
from .zoo import build, model_names, register_model
from . import afmoe, brumby, detection, dots3_note, glm_dsa, kimi_linear, longcat, mobilenet, transformer, vit  # noqa: F401,E402 — register zoo entries

__all__ = ["zoo", "build", "model_names", "register_model",
           "afmoe", "brumby", "dots3_note", "glm_dsa", "kimi_linear", "longcat", "mobilenet", "transformer", "vit"]
