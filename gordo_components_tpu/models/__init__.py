"""Model zoo: the reference's Keras estimators re-designed as Flax modules
trained by pure, jittable optax steps.

Reference parity map (``gordo_components/model/`` [UNVERIFIED — empty
reference mount, path-level citations only]):

- ``KerasAutoEncoder``      → :class:`DenseAutoEncoder`
- ``KerasLSTMAutoEncoder``  → :class:`LSTMAutoEncoder`
- ``KerasLSTMForecast``     → :class:`LSTMForecast`

The original class names are importable aliases so ported fleet configs that
reference ``gordo_components.model.models.KerasAutoEncoder`` resolve after a
single module-path rewrite (the serializer applies it automatically).
"""

from .base import GordoBase
from .register import register_model_factory, get_factory, list_kinds
from .models import (
    BaseFlaxEstimator,
    DenseAutoEncoder,
    LSTMAutoEncoder,
    LSTMForecast,
    MultiStepForecast,
    PatchTSTAutoEncoder,
    PatchTSTForecast,
    AfMoEForecast,
    MoEGQAForecast,
    MoEMLAForecast,
    KerasAutoEncoder,
    KerasLSTMAutoEncoder,
    KerasLSTMForecast,
)

# import for the registration side effects — every factory registers its kind
from .factories import afmoe, feedforward, lstm, moe_gqa, moe_mla, transformer  # noqa: F401

__all__ = [
    "GordoBase",
    "register_model_factory",
    "get_factory",
    "list_kinds",
    "BaseFlaxEstimator",
    "DenseAutoEncoder",
    "LSTMAutoEncoder",
    "LSTMForecast",
    "MultiStepForecast",
    "PatchTSTAutoEncoder",
    "PatchTSTForecast",
    "AfMoEForecast",
    "MoEGQAForecast",
    "MoEMLAForecast",
    "KerasAutoEncoder",
    "KerasLSTMAutoEncoder",
    "KerasLSTMForecast",
]
