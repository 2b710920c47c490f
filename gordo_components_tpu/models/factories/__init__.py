from .spec import ModelSpec, make_optimizer
from . import afmoe, feedforward, lstm, moe_gqa, moe_mla, transformer  # noqa: F401 — registration side effects

__all__ = ["ModelSpec", "make_optimizer", "afmoe", "feedforward", "lstm", "moe_gqa", "moe_mla", "transformer"]
